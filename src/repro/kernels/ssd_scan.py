"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

The selective state-space recurrence of one Mamba-2 layer, per head ``h``
(one group of ``N`` B/C channels shared by every head):

    state_t = exp(dt_t * A_h) * state_{t-1} + dt_t * x_t (outer) B_t
    y_t     = state_t . C_t                           (summed over N)

with ``x_t`` [P], ``B_t``/``C_t`` [N], ``state`` [P, N] and ``dt_t`` >= 0.
Positions at or past a row's true length take ``dt = 0``: the decay is
exp(0) = 1 and the input is zero, so bucket PAD leaves the state as the
row's last true token left it.

The chunked (SSD) form over chunks of ``L`` tokens: within a chunk the
decays are the differences of a cumulative sum ``cs`` of ``dt * A``, so

    y     = (C B^T  o  exp(cs_t - cs_s) [s <= t]  o  dt_s) x
            + exp(cs_t) * C state_0^T
    state = exp(cs_L) * state_0 + x^T (B o exp(cs_L - cs_s) dt_s)

are matrix products on the MXU; the state passes from chunk to chunk in
float32 scratch along the grid's last (sequential) axis.  The cumulative
sum is a product with an upper-triangular matrix of ones at ``fp32``
contract precision, and the decays and the state are float32; the three
matrix products take the activations' dtype (bf16 on the chip) with
float32 accumulation, as the reference CUDA kernels of Mamba-2 do.

Grid ``(B, H // heads_per_block, S // L)``: each step loads one chunk of
``heads_per_block`` heads, computes ``C B^T`` once for them, and walks
the heads.  The kernel's name, ``ssd_chunk_scan``, survives in the op
text of a profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_PER_BLOCK = 8


def _ssd_kernel(len_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, h_ref, state, *, chunk: int, heads: int,
                n_chunks: int):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state[...] = h0_ref[0]

    L = chunk
    f32 = jnp.float32
    mm = x_ref.dtype                       # matrix-product operand dtype
    pos = c * L + jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    dt = jnp.where(pos < len_ref[b], dt_ref[0], 0.0)          # [hb, L]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    upper = (row <= col).astype(f32)                          # s <= t
    cs = jnp.dot(dt * a_ref[...], upper, preferred_element_type=f32,
                 precision=jax.lax.Precision.HIGHEST)         # [hb, L]
    eye = row == col
    causal = col <= row                                       # [t, s]
    # each head's cumulative sum at the chunk's end, as rows over L and N
    # lanes (Mosaic broadcasts a column along lanes, not a [1, 1] along
    # both axes)
    last = jnp.sum(jnp.where(pos == c * L + L - 1, cs, 0.0), axis=1,
                   keepdims=True)                             # [hb, 1]
    last_l = jnp.broadcast_to(last, cs.shape)
    carry = jnp.exp(jnp.broadcast_to(last, (cs.shape[0], state.shape[2])))
    bm, cm = b_ref[0], c_ref[0]                               # [L, N]
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    g = jax.lax.dot_general(cm, bm, nt, preferred_element_type=f32)

    for j in range(heads):
        cs_r, dt_r = cs[j:j + 1], dt[j:j + 1]                 # [1, L]
        cs_c = jnp.sum(jnp.where(eye, cs_r, 0.0), axis=1, keepdims=True)
        decay = jnp.exp(jnp.where(causal, cs_c - cs_r, -jnp.inf))
        xj = x_ref[0, j]                                      # [L, P]
        h = state[j]                                          # [P, N]
        y = jnp.dot((g * decay * dt_r).astype(mm), xj,
                    preferred_element_type=f32)
        y = y + jnp.exp(cs_c) * jax.lax.dot_general(
            cm, h.astype(mm), nt, preferred_element_type=f32)
        w_r = jnp.exp(last_l[j:j + 1] - cs_r) * dt_r
        w_c = jnp.sum(jnp.where(eye, w_r, 0.0), axis=1, keepdims=True)
        state[j] = carry[j:j + 1] * h + jax.lax.dot_general(
            xj, (bm * w_c).astype(mm), tn, preferred_element_type=f32)
        y_ref[0, j] = y.astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _():
        h_ref[0] = state[...]


def ssd_chunk_scan_pallas(
    x: jnp.ndarray,        # [B, H, S, P]
    dt: jnp.ndarray,       # [B, H, S] float32, >= 0
    A: jnp.ndarray,        # [H] float32, < 0
    Bm: jnp.ndarray,       # [B, S, N]
    Cm: jnp.ndarray,       # [B, S, N]
    h0: jnp.ndarray,       # [B, H, P, N] float32
    length: jnp.ndarray,   # [B] int32 true tokens of each row
    *,
    chunk: int,
    interpret: bool = False,
):
    """(y [B, H, S, P] in x's dtype, final state [B, H, P, N] float32).
    ``S`` must be a multiple of ``chunk``; the caller pads."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    hb = min(HEADS_PER_BLOCK, H)
    assert H % hb == 0, (H, hb)
    n_chunks = S // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk, heads=hb,
                               n_chunks=n_chunks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # true lengths
        grid=(B, H // hb, n_chunks),
        in_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, h, c, *_: (b, h, c, 0)),
            pl.BlockSpec((1, hb, chunk), lambda b, h, c, *_: (b, h, c)),
            pl.BlockSpec((hb, 1), lambda b, h, c, *_: (h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c, *_: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c, *_: (b, c, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, h, c, *_: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, h, c, *_: (b, h, c, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, h, c, *_: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(length.astype(jnp.int32), x, dt.astype(jnp.float32),
      A.astype(jnp.float32).reshape(H, 1), Bm, Cm,
      h0.astype(jnp.float32))
