"""GQA attention block with full / sliding-window variants and KV caches.

Modes
-----
``full``     causal (or bidirectional) self-attention over the whole input;
             optionally emits a KV cache ("prefill").
``extend``   chunked prefill: queries are a suffix at static ``q_offset``;
             cached KV for ``[0, q_offset)`` is reused (the cascade
             fraction-extension primitive).  With ``q_start`` [B] (full
             attention only) row ``b``'s chunk starts at its own traced
             position instead, and ``q_offset`` bounds every start from
             above (the serving engine's one-pass operation suffix).
``decode``   one new token per sequence against the cache.

Caches are dicts ``{"k": [B, S_alloc, KV, Dh], "v": ...}``; keys are stored
*post-RoPE* so cache entries are position-final.  Sliding-window layers use
ring caches (``S_alloc = window``, slot = pos % window) — valid because
softmax attention is permutation-invariant over the key set once positions
are baked into the keys.

Paged serving: ``extend``/``decode`` also accept ``slots`` [B], in which
case ``cache`` is a persistent slot ARENA ``{"k": [N_rows, S_alloc, KV,
Dh], ...}`` shared by many documents — row ``slots[b]`` belongs to batch
row ``b`` (the last arena row is the serving scratch/padding sentinel).
Chunk and decode KV are scattered into the addressed rows in place and
attention reads the arena through the paged kernels
(``ops.attention_paged`` / ``ops.arena_decode_attention``) — no [B, S]
gather copy.  Paged mode supports full causal attention only (no sliding
window / cross-attention); ``models.model.LM.supports_paged_kv`` gates it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .layers import apply_rope, apply_mrope, init_dense, init_rmsnorm, rmsnorm_apply
from .runtime import Runtime


def init_attention(rng, d: int, h: int, kv: int, dh: int, qk_norm: bool,
                   dtype=jnp.bfloat16) -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p = {
        "wq": init_dense(k1, (d, h * dh), dtype).reshape(d, h, dh),
        "wk": init_dense(k2, (d, kv * dh), dtype).reshape(d, kv, dh),
        "wv": init_dense(k3, (d, kv * dh), dtype).reshape(d, kv, dh),
        "wo": init_dense(k4, (h * dh, d), dtype).reshape(h, dh, d),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(dh)
        p["k_norm"] = init_rmsnorm(dh)
    return p


def spec_attention(kv_sharded: bool, qk_norm: bool) -> Dict[str, Any]:
    kv_spec = (None, "tp", None) if kv_sharded else (None, None, None)
    s = {
        "wq": (None, "tp", None),
        "wk": kv_spec,
        "wv": kv_spec,
        "wo": ("tp", None, None),
    }
    if qk_norm:
        s["q_norm"] = {"scale": (None,)}
        s["k_norm"] = {"scale": (None,)}
    return s


def init_kv_cache(batch: int, s_alloc: int, kv: int, dh: int, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, s_alloc, kv, dh), dtype),
        "v": jnp.zeros((batch, s_alloc, kv, dh), dtype),
    }


def kv_cache_shape(batch: int, s_alloc: int, kv: int, dh: int, dtype=jnp.bfloat16):
    return {
        "k": jax.ShapeDtypeStruct((batch, s_alloc, kv, dh), dtype),
        "v": jax.ShapeDtypeStruct((batch, s_alloc, kv, dh), dtype),
    }


def spec_kv_cache(kv_sharded: bool, sp: bool):
    """Cache logical spec: batch over dp; optionally sequence over sp(data)."""
    seq = "sp" if sp else None
    kv = "tp" if kv_sharded else None
    return {"k": ("dp", seq, kv, None), "v": ("dp", seq, kv, None)}


def _project_qkv(p, x, positions, *, theta, qk_norm, mrope_sections=None,
                 positions3=None, norm_eps=1e-6):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, norm_eps)
    if mrope_sections is not None:
        q = apply_mrope(q, positions3, theta, mrope_sections)
        k = apply_mrope(k, positions3, theta, mrope_sections)
    elif positions is not None:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def chunk_positions(start: jnp.ndarray, length: int) -> jnp.ndarray:
    """[B, length] cache positions of a chunk whose row ``b`` starts at
    ``start[b]``: a ragged-start extend's RoPE positions and KV writes,
    and the serving undo log's window."""
    return (start.astype(jnp.int32)[:, None]
            + jnp.arange(length, dtype=jnp.int32)[None])


def attention_apply(
    p: Dict[str, Any],
    x: jnp.ndarray,                  # [B, S, D]
    *,
    rt: Runtime,
    mode: str = "full",              # full | extend | decode
    causal: bool = True,
    window: Optional[int] = None,
    positions: Optional[jnp.ndarray] = None,   # [B, S] absolute positions
    positions3: Optional[jnp.ndarray] = None,  # [B, S, 3] for M-RoPE
    mrope_sections=None,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    cache_len: Optional[jnp.ndarray] = None,   # [B] int32 valid cache entries
    q_offset: int = 0,               # static, mode=extend
    kv_len: Optional[jnp.ndarray] = None,      # [B] true (unpadded) length
                                               # incl. this chunk, mode=extend
    q_start: Optional[jnp.ndarray] = None,     # [B] per-row chunk start
                                               # (<= q_offset), mode=extend
    slots: Optional[jnp.ndarray] = None,       # [B] arena rows (paged serving)
    block_tables: Optional[jnp.ndarray] = None,  # [B, S_alloc // block] rows
                                               # per cache block (prefix
                                               # sharing); reads only — all
                                               # writes go through ``slots``
    want_cache: bool = False,
    qk_norm: bool = False,
    theta: float = 10_000.0,
    norm_eps: float = 1e-6,
    use_rope: bool = True,           # whisper uses absolute sinusoids
                                     # instead; granite uses no positions
    sm_scale: Optional[float] = None,  # None: 1/sqrt(head_dim)
    kv_ctx: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # cross-attn K,V
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    B, S, D = x.shape
    dh = p["wq"].shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dh)

    if kv_ctx is not None:
        # cross attention (whisper decoder): kv precomputed from encoder
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k, v = kv_ctx
        out = ops.attention(q, k, v, causal=False, impl=rt.attn_impl,
                            sm_scale=sm_scale, block_q=rt.block_q,
                            block_kv=rt.block_kv)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return out, None

    if positions is None and positions3 is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if mrope_sections is not None and positions3 is None:
        # text-only input on an M-RoPE arch: t = h = w = position
        positions3 = jnp.broadcast_to(
            positions[..., None], positions.shape + (3,)).astype(jnp.int32)

    q, k, v = _project_qkv(
        p, x, positions if use_rope else None, theta=theta, qk_norm=qk_norm,
        mrope_sections=mrope_sections if use_rope else None,
        positions3=positions3, norm_eps=norm_eps,
    )

    new_cache = None

    if mode == "full":
        out = ops.attention(
            q, k, v, causal=causal, window=window, impl=rt.attn_impl,
            sm_scale=sm_scale, block_q=rt.block_q, block_kv=rt.block_kv,
        )
        if want_cache:
            if window is not None and window > 0:
                s_keep = min(S, window)
                # ring layout: absolute position pos -> slot pos % window
                kk = k[:, -s_keep:]
                vv = v[:, -s_keep:]
                pos_tail = positions[:, -s_keep:]
                ring = pos_tail % window                        # [B, s_keep]
                ck = jnp.zeros((B, window) + k.shape[2:], k.dtype)
                cv = jnp.zeros_like(ck)
                bidx = jnp.arange(B)[:, None]
                ck = ck.at[bidx, ring].set(kk)
                cv = cv.at[bidx, ring].set(vv)
                new_cache = {"k": ck, "v": cv}
            else:
                new_cache = {"k": k, "v": v}
    elif mode == "extend":
        assert cache is not None
        assert q_start is None or window in (None, 0), \
            "ragged-start extend (q_start) supports full attention only"
        if slots is not None:
            # paged extend: ``cache`` is the slot arena [N_rows, S, KV, Dh];
            # scatter the chunk's KV into the addressed rows, then attend
            # in place through the paged kernel (no [B, S] gather)
            assert window in (None, 0), \
                "paged extend supports full attention only"
            kv_valid = min(q_offset + S, cache["k"].shape[1])
            # the arena may store KV compressed (bf16 for f32 models):
            # quantize on the scatter; the kernels upcast to f32 at read
            if q_start is None:
                rows, cols = slots, slice(q_offset, q_offset + S)
            else:
                rows, cols = slots[:, None], chunk_positions(q_start, S)
            ck = cache["k"].at[rows, cols].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[rows, cols].set(v.astype(cache["v"].dtype))
            out = ops.attention_paged(
                q, ck, cv, slots, kv_valid=kv_valid,
                block_tables=block_tables, causal=causal,
                q_offset=q_offset, kv_len=kv_len, q_start=q_start,
                impl=rt.attn_impl, sm_scale=sm_scale, block_q=rt.block_q,
                block_kv=rt.block_kv,
            )
            if want_cache:
                new_cache = {"k": ck, "v": cv}
        elif window is not None and window > 0 and q_offset == 0:
            # fresh prefill routed through extend (cache preallocated but
            # empty): use the blocked kernel directly — the ragged
            # ring-merge path below would materialize [S, W+S] scores
            # (measured 17+ GB/layer/chip on gemma3 prefill_32k; see
            # EXPERIMENTS.md §Perf iteration 1).
            out = ops.attention(
                q, k, v, causal=causal, window=window, kv_len=kv_len,
                impl=rt.attn_impl, sm_scale=sm_scale, block_q=rt.block_q,
                block_kv=rt.block_kv,
            )
            if want_cache:
                Wn = cache["k"].shape[1]
                s_keep = min(S, Wn)
                kk = k[:, -s_keep:]
                vv = v[:, -s_keep:]
                pos_tail = positions[:, -s_keep:]
                ring = pos_tail % Wn
                bidx = jnp.arange(B)[:, None]
                ck = cache["k"].at[bidx, ring].set(kk)
                cv = cache["v"].at[bidx, ring].set(vv)
                new_cache = {"k": ck, "v": cv}
        elif window is not None and window > 0:
            # small-window extend: attend over ring cache + new chunk with
            # exact per-key absolute positions (naive masked path; cheap at
            # window scale).  Positions of ring slots are recoverable from
            # slot index and current absolute offset.
            Wn = cache["k"].shape[1]
            slot = jnp.arange(Wn)[None, :]                       # [1, W]
            # exact slot->pos map: pos = largest p < q_offset with p% W == slot
            kpos = slot + ((q_offset - 1 - slot) // Wn) * Wn
            k_all = jnp.concatenate([cache["k"], k], axis=1)
            v_all = jnp.concatenate([cache["v"], v], axis=1)
            kpos_all = jnp.concatenate(
                [jnp.broadcast_to(kpos, (B, Wn)),
                 positions.astype(jnp.int32)], axis=1)           # [B, W+S]
            qpos = positions[..., None]                          # [B,S,1]
            valid = (kpos_all[:, None, :] <= qpos) & \
                    (kpos_all[:, None, :] > qpos - window) & \
                    (kpos_all[:, None, :] >= 0)
            if kv_len is not None:
                valid &= kpos_all[:, None, :] < kv_len[:, None, None]
            g = q.shape[2] // k_all.shape[2]
            kf = jnp.repeat(k_all.astype(jnp.float32), g, axis=2)
            vf = jnp.repeat(v_all.astype(jnp.float32), g, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * sm_scale, kf)
            s = jnp.where(valid[:, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", pr, vf).astype(x.dtype)
            if want_cache:
                ring = positions % window
                bidx = jnp.arange(B)[:, None]
                ck = cache["k"].at[bidx, ring].set(k)
                cv = cache["v"].at[bidx, ring].set(v)
                new_cache = {"k": ck, "v": cv}
        else:
            # full-attention extend: write new kv at [q_offset, q_offset+S)
            # (or at [q_start[b], q_start[b]+S) of row b)
            if q_start is None:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), q_offset, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), q_offset, 1)
            else:
                rows = jnp.arange(B)[:, None]
                cols = chunk_positions(q_start, S)
                ck = cache["k"].at[rows, cols].set(k.astype(cache["k"].dtype))
                cv = cache["v"].at[rows, cols].set(v.astype(cache["v"].dtype))
            kv_valid = q_offset + S
            out = ops.attention(
                q, ck[:, :kv_valid] if kv_valid < ck.shape[1] else ck,
                cv[:, :kv_valid] if kv_valid < cv.shape[1] else cv,
                causal=causal, q_offset=q_offset, kv_len=kv_len,
                q_start=q_start, impl=rt.attn_impl, sm_scale=sm_scale,
                block_q=rt.block_q, block_kv=rt.block_kv,
            )
            if want_cache:
                new_cache = {"k": ck, "v": cv}
    elif mode == "decode":
        assert cache is not None and cache_len is not None and S == 1
        # decode masks by cache_len (valid cache entries); a per-row
        # kv_len override is an extend-only contract — reject it loudly
        # rather than silently ignoring it
        assert kv_len is None, "kv_len is mode='extend' only; decode " \
            "masks by cache_len"
        if slots is not None:
            # paged decode: write the token's KV at (slots[b], cache_len[b])
            # and read the arena in place — slot ids resolve inside the
            # kernel (scalar-prefetch SMEM), eliminating the gather copy
            assert window in (None, 0), \
                "paged decode supports full attention only"
            ck = cache["k"].at[slots, cache_len].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[slots, cache_len].set(
                v[:, 0].astype(cache["v"].dtype))
            out1 = ops.arena_decode_attention(
                q[:, 0], ck, cv, slots, cache_len + 1,
                block_tables=block_tables, sm_scale=sm_scale,
                impl=rt.attn_impl, block_kv=rt.block_kv,
            )
        else:
            if window is not None and window > 0:
                Wn = cache["k"].shape[1]
                ring = (positions[:, 0] % Wn)
                bidx = jnp.arange(B)
                ck = cache["k"].at[bidx, ring].set(k[:, 0])
                cv = cache["v"].at[bidx, ring].set(v[:, 0])
                kv_valid = jnp.minimum(cache_len + 1, Wn)
            else:
                bidx = jnp.arange(B)
                ck = cache["k"].at[bidx, cache_len].set(k[:, 0])
                cv = cache["v"].at[bidx, cache_len].set(v[:, 0])
                kv_valid = cache_len + 1
            if rt.sp_decode and rt.mesh is not None and window in (None, 0):
                from ..distributed.collectives import sp_decode_attention
                out1 = sp_decode_attention(
                    q[:, 0], ck, cv, kv_valid, mesh=rt.mesh,
                    sm_scale=sm_scale)
            else:
                out1 = ops.decode_attention(
                    q[:, 0], ck, cv, kv_valid, sm_scale=sm_scale,
                    impl=rt.attn_impl, block_kv=rt.block_kv,
                )
        out = out1[:, None]
        new_cache = {"k": ck, "v": cv}
    else:
        raise ValueError(mode)

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache
