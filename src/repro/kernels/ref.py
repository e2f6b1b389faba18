"""Pure-jnp reference oracles for every Pallas kernel.

These are deliberately naive (materialize the full score matrix, fp32
softmax) — they define correctness for small shapes; kernels are validated
against them with ``interpret=True`` sweeps in tests/.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mha_reference(
    q: jnp.ndarray,             # [B, Sq, Hq, Dh]
    k: jnp.ndarray,             # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,             # [B, Skv, Hkv, Dh]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,   # [B] valid kv length (padding mask)
    q_start: Optional[jnp.ndarray] = None,  # [B] per-row first query position
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Grouped-query attention reference with prefix-extend semantics.

    Query position i (0-based within q) has absolute position q_offset + i,
    or q_start[b] + i in row b when ``q_start`` is given.
    ``causal`` masks kv positions > absolute q position; ``window`` further
    restricts to kv positions > abs_q - window.
    """
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to q heads
    kf = jnp.repeat(kf, g, axis=2)
    vf = jnp.repeat(vf, g, axis=2)

    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)    # [B, Hq, Sq, Skv]

    start = (jnp.full((B,), q_offset, jnp.int32) if q_start is None
             else q_start.astype(jnp.int32))
    qpos = start[:, None, None] + jnp.arange(Sq)[None, :, None]  # [B, Sq, 1]
    kpos = jnp.arange(Skv)[None, None, :]              # [1, 1, Skv]
    mask = jnp.ones((B, Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None and window > 0:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len[:, None, None]
    mask_b = jnp.broadcast_to(mask[:, None], scores.shape)
    scores = jnp.where(mask_b, scores, -jnp.inf)
    # rows that are fully masked produce zeros, not NaN
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


def decode_reference(
    q: jnp.ndarray,             # [B, Hq, Dh] single query token
    k: jnp.ndarray,             # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,
    *,
    kv_len: Optional[jnp.ndarray] = None,   # [B] number of valid cache slots
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    out = mha_reference(
        q[:, None], k, v,
        causal=False, window=None, q_offset=0,
        kv_len=kv_len, sm_scale=sm_scale,
    )
    return out[:, 0]


def relevance_reference(
    x: jnp.ndarray,             # [C, T, D] chunk token embeddings
    lengths: jnp.ndarray,       # [C] valid token count per chunk
    w: jnp.ndarray,             # [D]
    b: jnp.ndarray,             # [] bias
) -> jnp.ndarray:
    """sigmoid(meanpool(x) @ w + b) per chunk -> [C] relevance scores."""
    mask = (jnp.arange(x.shape[1])[None, :] < lengths[:, None]).astype(jnp.float32)
    summed = jnp.einsum("ctd,ct->cd", x.astype(jnp.float32), mask)
    denom = jnp.maximum(lengths.astype(jnp.float32), 1.0)[:, None]
    pooled = summed / denom
    logit = pooled @ w.astype(jnp.float32) + b.astype(jnp.float32)
    return jax.nn.sigmoid(logit)


def ssd_reference(
    x: jnp.ndarray,             # [B, S, H, P]
    dt: jnp.ndarray,            # [B, S, H] (>= 0)
    A: jnp.ndarray,             # [H] (< 0)
    Bm: jnp.ndarray,            # [B, S, N]
    Cm: jnp.ndarray,            # [B, S, N]
    h0: jnp.ndarray,            # [B, H, P, N]
    length: jnp.ndarray,        # [B] true tokens
):
    """The Mamba-2 selective scan one token at a time, in float32:
    state = exp(dt A) state + dt x (outer) B, y = state . C, with dt = 0 at
    and past each row's true length.  -> (y [B, S, H, P], state)."""
    f32 = jnp.float32
    S = x.shape[1]
    dt = jnp.where(jnp.arange(S)[None, :, None] < length[:, None, None],
                   dt.astype(f32), 0.0)

    def step(h, xs):
        xt, dtt, bt, ct = xs                 # [B,H,P], [B,H], [B,N], [B,N]
        h = (jnp.exp(dtt * A.astype(f32))[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct)

    xs = (jnp.moveaxis(x.astype(f32), 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm.astype(f32), 1, 0),
          jnp.moveaxis(Cm.astype(f32), 1, 0))
    h, ys = jax.lax.scan(step, h0.astype(f32), xs)
    return jnp.moveaxis(ys, 0, 1), h
