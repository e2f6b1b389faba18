"""Fused chunk relevance scoring Pallas kernel.

Document restructuring (paper §4) scores every chunk of every incoming
document with a logistic-regression head over mean-pooled chunk embeddings.
At serving scale this runs on *every* document before the cascade, so the
mean-pool and the score are fused: the [C, D] pooled matrix is never
materialized in HBM — each grid step pools a tile of chunks in VMEM and
immediately reduces it against the classifier weights.

x: [C, T, D] chunk token embeddings, lengths: [C], w: [D], b: [1].
Output: [C] sigmoid relevance scores (f32).

The chunk tile ``block_c`` defaults to what fits VMEM: each chunk costs
its double-buffered [T, D] input block plus the kernel's f32 copy, and
the tile is sized to ``VMEM_BUDGET`` (half of Mosaic's default 16 MiB
scope, leaving room for temporaries) in multiples of 8 sublanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET = 8 << 20


def default_block_c(t: int, d: int, itemsize: int) -> int:
    """Largest multiple of 8 chunks whose blocks fit ``VMEM_BUDGET``
    (never below 8, the (8, 1) length/output tile's sublane minimum)."""
    per_chunk = t * d * (2 * itemsize + 4)
    return max(8, VMEM_BUDGET // per_chunk // 8 * 8)


def _relevance_kernel(x_ref, len_ref, w_ref, b_ref, o_ref, *, block_c: int, t: int):
    x = x_ref[...].astype(jnp.float32)                    # [bc, T, D]
    lengths = len_ref[...].astype(jnp.float32)            # [bc, 1]
    w = w_ref[...].astype(jnp.float32)                    # [1, D]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (block_c, t), 1)
    mask = (tpos < lengths.astype(jnp.int32)).astype(jnp.float32)  # [bc, T]
    # fused: score_c = (sum_t mask*x[c,t,:] @ w) / len_c
    xw = jax.lax.dot_general(
        x.reshape(block_c * t, -1), w,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(block_c, t)                                  # [bc, T]
    summed = jnp.sum(xw * mask, axis=-1)                   # [bc]
    denom = jnp.maximum(lengths[:, 0], 1.0)
    logit = summed / denom + b_ref[0, 0]
    o_ref[...] = jax.nn.sigmoid(logit)[:, None]


def relevance_score_pallas(
    x: jnp.ndarray,          # [C, T, D]
    lengths: jnp.ndarray,    # [C]
    w: jnp.ndarray,          # [D]
    b: jnp.ndarray,          # [] or [1]
    *,
    block_c: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    C, T, D = x.shape
    if block_c is None:
        block_c = default_block_c(T, D, x.dtype.itemsize)
    block_c = min(block_c, C)
    # Ragged chunk counts (real corpora rarely land on a block multiple):
    # pad the chunk axis with zero-length chunks and slice them back off.
    # Padded rows score sigmoid(b) but are masked out of the pool (length 0)
    # and dropped below, so they never reach callers.
    c_pad = (-C) % block_c
    if c_pad:
        x = jnp.pad(x, ((0, c_pad), (0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, c_pad))
    c_full = C + c_pad
    nc = c_full // block_c

    kernel = functools.partial(_relevance_kernel, block_c=block_c, t=T)
    out = pl.pallas_call(
        kernel,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((block_c, T, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c_full, 1), jnp.float32),
        interpret=interpret,
    )(x, lengths.reshape(c_full, 1).astype(jnp.int32), w.reshape(1, D),
      jnp.asarray(b, jnp.float32).reshape(1, 1))
    return out[:C, 0]
