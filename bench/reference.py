"""Plain Qwen3 forward pass in float32, independent of the program.

It follows the published Qwen3 decoder (arXiv:2505.09388; Hugging Face
``Qwen3ForCausalLM``): RMSNorm pre-norms, GQA attention with per-head
RMSNorm on q and k before rotary embeddings (half-split rotation,
``rope_theta``), causal softmax, SwiGLU MLP, final RMSNorm, head tied to
the embedding.  Every matrix product runs at ``highest`` precision on
float32 copies of the weights.  No cache, no batching, no kernels: one
sequence at a time, layer by layer under ``lax.scan``.

``tokenize`` is a copy of the word-hash tokenizer the served documents
go through (blake2b of the lower-cased word), so the reference reads the
same token ids without calling the program.

With ``control=True`` every matrix is first rounded to fp8 (e4m3) with
one scale per output channel, layer by layer as it is used: the step
below bf16 that the output check must catch.  (int8 with per-channel
scales reads within 3x of the served bf16 program on this check, so it
is not the control; see PERF.md.)
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CLASS_BASE = 8            # class c is answered by token CLASS_BASE + c
FIRST_WORD_ID = 16        # ids below are specials and class tokens
PAD_TO = 256              # prompts are padded to a multiple: few programs


def tokenize(text: str, vocab_size: int) -> List[int]:
    span = vocab_size - FIRST_WORD_ID
    return [FIRST_WORD_ID + int.from_bytes(
        hashlib.blake2b(w.lower().encode(), digest_size=4).digest(),
        "little") % span for w in text.split()]


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # [S, dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _forward(params, tokens, n_valid, class_ids, *, theta, eps, control):
    """Class logits at position ``n_valid - 1`` of ``tokens`` [S] (the
    positions past ``n_valid`` are padding and are never attended)."""
    def f32(a, in_axes=None):
        a = a.astype(jnp.float32)
        return a if not control or in_axes is None else _fp8_round(a, in_axes)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32(params["embed"]["table"][tokens], (1,))
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["norm1"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, f32(a["wq"], (0,)))
        k = jnp.einsum("sd,dhk->shk", h, f32(a["wk"], (0,)))
        v = jnp.einsum("sd,dhk->shk", h, f32(a["wv"], (0,)))
        q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, theta)
        k = _rope(_rms(k, a["k_norm"]["scale"], eps), pos, theta)
        g = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", o, f32(a["wo"], (0, 1)))
        h = _rms(x, p["norm2"]["scale"], eps)
        m = p["mlp"]
        up = jax.nn.silu(h @ f32(m["w1"], (0,))) * (h @ f32(m["w3"], (0,)))
        return x + up @ f32(m["w2"], (0,)), None

    x, _ = jax.lax.scan(layer, x, params["stages"][0])
    last = _rms(jax.lax.dynamic_index_in_dim(x, n_valid - 1, 0, False),
                params["final_norm"]["scale"], eps)
    return f32(params["embed"]["table"][class_ids], (1,)) @ last


@functools.lru_cache(maxsize=None)
def _compiled(theta: float, eps: float, control: bool):
    return jax.jit(functools.partial(_forward, theta=theta, eps=eps,
                                     control=control))


def class_logits(params, model: Mapping[str, Any], tokens: Sequence[int],
                 n_classes: int, control: bool = False) -> np.ndarray:
    """Class logits after the last of ``tokens``."""
    n = len(tokens)
    S = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    ids = jnp.asarray([CLASS_BASE + c for c in range(n_classes)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = _compiled(float(model["rope_theta"]),
                        float(model["rms_norm_eps"]), bool(control))(
            params, jnp.asarray(toks), jnp.int32(n), ids)
    return np.asarray(out, np.float64)


FP8_MAX = 448.0           # largest finite float8_e4m3fn


def _fp8_round(w: jnp.ndarray, in_axes: Sequence[int]) -> jnp.ndarray:
    """``w`` rounded to fp8 e4m3 with one scale per output channel (the
    max over ``in_axes``, the axes a matrix product sums over)."""
    amax = jnp.max(jnp.abs(w), axis=tuple(in_axes), keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
