"""What every architecture's float32 reference shares, independent of the
program: the prompt's token ids, the class rows, the padding, the
precision and the control's rounding.  The forward pass itself is the
architecture's own (``class_logits`` of ``arch/<model_type>.py``): one
sequence at a time, no cache, no batching, no kernels, every matrix
product at ``highest`` precision on float32 copies of the weights.

``tokenize`` is a copy of the word-hash tokenizer the served documents
go through (blake2b of the lower-cased word), so the reference reads the
same token ids without calling the program.

With ``control=True`` every matrix is first rounded to fp8 (e4m3) with
one scale per output channel, layer by layer as it is used
(``as_f32``): the step below bf16 that the output check must catch.
(int8 with per-channel scales reads within 3x of the served bf16 program
on this check, so it is not the control; see PERF.md.)
"""
from __future__ import annotations

import hashlib
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CLASS_BASE = 8            # class c is answered by token CLASS_BASE + c
FIRST_WORD_ID = 16        # ids below are specials and class tokens
PAD_TO = 256              # prompts are padded to a multiple: few programs
FP8_MAX = 448.0           # largest finite float8_e4m3fn


def tokenize(text: str, vocab_size: int) -> List[int]:
    span = vocab_size - FIRST_WORD_ID
    return [FIRST_WORD_ID + int.from_bytes(
        hashlib.blake2b(w.lower().encode(), digest_size=4).digest(),
        "little") % span for w in text.split()]


def last_class_logits(forward: Callable, params, tokens: Sequence[int],
                      n_classes: int) -> np.ndarray:
    """Class logits after the last of ``tokens``: ``forward(params,
    tokens [S], n_valid, class_ids)`` (jitted) on the prompt padded to a
    multiple of ``PAD_TO``, at ``highest`` matrix-product precision."""
    n = len(tokens)
    S = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    ids = jnp.asarray([CLASS_BASE + c for c in range(n_classes)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = forward(params, jnp.asarray(toks), jnp.int32(n), ids)
    return np.asarray(out, np.float64)


def as_f32(w: jnp.ndarray, in_axes: Optional[Sequence[int]],
           control: bool) -> jnp.ndarray:
    """A weight as the reference multiplies it: float32, or in the
    control first rounded to fp8 where ``in_axes`` (the axes a matrix
    product sums over) is given."""
    w = w.astype(jnp.float32)
    return w if not control or in_axes is None else fp8_round(w, in_axes)


def fp8_round(w: jnp.ndarray, in_axes: Sequence[int]) -> jnp.ndarray:
    """``w`` rounded to fp8 e4m3 with one scale per output channel (the
    max over ``in_axes``, the axes a matrix product sums over)."""
    amax = jnp.max(jnp.abs(w), axis=tuple(in_axes), keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
