"""Block layer: sequence mixer (+ optional FFN) with pre-norms.

A block is one transformer-ish layer of a given *kind* (config.py constants):
attention (full / sliding-window / bidirectional), mLSTM, sLSTM, RG-LRU or
Mamba-2.
Every block exposes the same functional surface —

    init_block / spec_block                   parameters
    init_block_state / block_state_shape /    decode-time state (KV cache or
        spec_block_state                      recurrent state)
    block_apply(mode=train|prefill|extend|decode)

so the model can scan over heterogeneous superblock patterns uniformly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import (ATTN_FULL, ATTN_LOCAL, ENC_ATTN, MAMBA2, MLSTM, RGLRU,
                      SLSTM, ResolvedConfig)
from . import ssm
from .attention import (attention_apply, init_attention, init_kv_cache,
                        kv_cache_shape, spec_attention, spec_kv_cache)
from .layers import (init_mlp, init_rmsnorm, mlp_apply, rmsnorm_apply,
                     spec_mlp, spec_rmsnorm)
from .moe import init_moe, moe_apply, spec_moe
from .runtime import Runtime

_ATTN_KINDS = (ATTN_FULL, ATTN_LOCAL, ENC_ATTN)


def _has_ffn(rcfg: ResolvedConfig) -> bool:
    return rcfg.base.moe is not None or rcfg.base.d_ff > 0


def _lru_width(rcfg: ResolvedConfig) -> int:
    return rcfg.base.d_model  # Griffin uses lru_width == d_model for 2b


def _mamba2_sizes(rcfg: ResolvedConfig) -> Tuple[int, int, int, int]:
    b = rcfg.base
    return b.ssm_heads, b.ssm_head_dim, b.ssm_state, b.ssm_conv


# ---------------------------------------------------------------------------
# init / spec
# ---------------------------------------------------------------------------

def init_block(rng, rcfg: ResolvedConfig, kind: str, dtype=jnp.bfloat16):
    b = rcfg.base
    d = b.d_model
    k1, k2, k3 = jax.random.split(rng, 3)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(d)}
    if kind in _ATTN_KINDS:
        p["attn"] = init_attention(
            k1, d, rcfg.padded_heads, rcfg.padded_kv_heads, rcfg.head_dim,
            b.qk_norm, dtype)
    elif kind == MLSTM:
        p["mlstm"] = ssm.init_mlstm(k1, d, b.num_heads, dtype)
    elif kind == SLSTM:
        p["slstm"] = ssm.init_slstm(k1, d, b.num_heads, dtype)
    elif kind == RGLRU:
        p["rglru"] = ssm.init_rglru(k1, d, _lru_width(rcfg), dtype)
    elif kind == MAMBA2:
        p["mamba"] = ssm.init_mamba2(k1, d, *_mamba2_sizes(rcfg), dtype)
    else:
        raise ValueError(kind)
    if _has_ffn(rcfg):
        p["norm2"] = init_rmsnorm(d)
        if b.moe is not None:
            p["moe"] = init_moe(k2, d, b.d_ff, b.moe.num_experts, dtype)
        else:
            p["mlp"] = init_mlp(k2, d, b.d_ff, dtype)
    return p


def spec_block(rcfg: ResolvedConfig, kind: str):
    b = rcfg.base
    kv_sharded = rcfg.padded_kv_heads >= rcfg.tp
    s: Dict[str, Any] = {"norm1": spec_rmsnorm()}
    if kind in _ATTN_KINDS:
        s["attn"] = spec_attention(kv_sharded, b.qk_norm)
    elif kind == MLSTM:
        s["mlstm"] = ssm.spec_mlstm()
    elif kind == SLSTM:
        s["slstm"] = ssm.spec_slstm()
    elif kind == RGLRU:
        s["rglru"] = ssm.spec_rglru()
    elif kind == MAMBA2:
        s["mamba"] = ssm.spec_mamba2()
    if _has_ffn(rcfg):
        s["norm2"] = spec_rmsnorm()
        if b.moe is not None:
            strategy = b.moe.strategy
            s["moe"] = spec_moe(strategy)
        else:
            s["mlp"] = spec_mlp()
    return s


# ---------------------------------------------------------------------------
# decode/serve state
# ---------------------------------------------------------------------------

def _attn_alloc(rcfg: ResolvedConfig, kind: str, s_alloc: int) -> int:
    if kind == ATTN_LOCAL:
        return min(rcfg.base.sliding_window, s_alloc)
    return s_alloc


def init_block_state(rcfg: ResolvedConfig, kind: str, batch: int,
                     s_alloc: int, dtype=jnp.bfloat16, kv_dtype=None):
    b = rcfg.base
    if kind in _ATTN_KINDS:
        # kv_dtype compresses ATTENTION caches only (the serving arena's
        # storage dtype); recurrent SSM states keep the compute dtype
        return init_kv_cache(
            batch, _attn_alloc(rcfg, kind, s_alloc),
            rcfg.padded_kv_heads, rcfg.head_dim, kv_dtype or dtype)
    if kind == MLSTM:
        return ssm.init_mlstm_state(batch, b.num_heads, b.d_model // b.num_heads)
    if kind == SLSTM:
        return ssm.init_slstm_state(batch, b.d_model)
    if kind == RGLRU:
        return ssm.init_rglru_state(batch, _lru_width(rcfg))
    if kind == MAMBA2:
        return ssm.init_mamba2_state(batch, *_mamba2_sizes(rcfg), dtype)
    raise ValueError(kind)


def block_state_shape(rcfg: ResolvedConfig, kind: str, batch: int,
                      s_alloc: int, dtype=jnp.bfloat16, kv_dtype=None):
    b = rcfg.base
    if kind in _ATTN_KINDS:
        return kv_cache_shape(
            batch, _attn_alloc(rcfg, kind, s_alloc),
            rcfg.padded_kv_heads, rcfg.head_dim, kv_dtype or dtype)
    if kind == MLSTM:
        return ssm.mlstm_state_shape(batch, b.num_heads, b.d_model // b.num_heads)
    if kind == SLSTM:
        return ssm.slstm_state_shape(batch, b.d_model)
    if kind == RGLRU:
        return ssm.rglru_state_shape(batch, _lru_width(rcfg))
    if kind == MAMBA2:
        return ssm.mamba2_state_shape(batch, *_mamba2_sizes(rcfg), dtype)
    raise ValueError(kind)


def spec_block_state(rcfg: ResolvedConfig, kind: str, *, batch_sharded: bool,
                     seq_sharded: bool):
    """Logical spec for a block's state.

    ``batch_sharded``: batch dim over dp (requires batch % dp == 0).
    ``seq_sharded``: KV sequence dim over data (long-context SP-KV; only
    full-attention caches — ring caches and recurrent states stay local).
    """
    kv_sharded = rcfg.padded_kv_heads >= rcfg.tp
    dp = "dp" if batch_sharded else None
    if kind in _ATTN_KINDS:
        sp = "sp" if (seq_sharded and kind != ATTN_LOCAL) else None
        kv = "tp" if kv_sharded else None
        return {"k": (dp, sp, kv, None), "v": (dp, sp, kv, None)}
    if kind == MLSTM:
        s = ssm.spec_mlstm_state()
    elif kind == SLSTM:
        s = ssm.spec_slstm_state()
    elif kind == RGLRU:
        s = ssm.spec_rglru_state()
    elif kind == MAMBA2:
        s = ssm.spec_mamba2_state()
    else:
        raise ValueError(kind)
    if not batch_sharded:
        s = jax.tree.map(
            lambda t: tuple(None if a == "dp" else a for a in t), s,
            is_leaf=lambda x: isinstance(x, tuple))
    return s


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def block_apply(
    p: Dict[str, Any],
    x: jnp.ndarray,                    # [B, S, D]
    *,
    kind: str,
    rcfg: ResolvedConfig,
    rt: Runtime,
    mode: str,                         # train | prefill | extend | decode
    state: Optional[Any] = None,
    cache_len: Optional[jnp.ndarray] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,      # [B] true length, mode=extend
    q_start: Optional[jnp.ndarray] = None,     # [B] chunk starts, mode=extend
    slots: Optional[jnp.ndarray] = None,       # [B] arena rows (paged serving)
    block_tables: Optional[jnp.ndarray] = None,  # [B, nblocks] rows per cache
                                               # block (prefix sharing)
    positions: Optional[jnp.ndarray] = None,
    positions3: Optional[jnp.ndarray] = None,
    dp_spec=None,
    commit_recurrent: bool = True,
) -> Tuple[jnp.ndarray, Optional[Any], jnp.ndarray]:
    """Returns (y, new_state, moe_aux_loss).

    Recurrent kinds: an extend at offset 0 (no ``q_start``) starts a new
    sequence, so it starts from the initial state whatever ``state``
    holds (a recycled arena row's previous document).  Mamba-2 also
    addresses arena rows by ``slots``, masks each row to its true length
    and, with ``commit_recurrent`` False, leaves ``state`` as it came in
    (the serving op suffix reads the document's state, never writes
    it)."""
    b = rcfg.base
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm_apply(p["norm1"], x, b.norm_eps)
    fresh = (kind not in _ATTN_KINDS and mode == "extend" and q_offset == 0
             and q_start is None)

    if kind in _ATTN_KINDS:
        attn_mode = {"train": "full", "prefill": "full",
                     "extend": "extend", "decode": "decode"}[mode]
        window = b.sliding_window if kind == ATTN_LOCAL else None
        assert slots is None or (kind == ATTN_FULL and window is None), \
            "paged serving (slots) supports full-attention blocks only"
        with jax.named_scope("attention"):
            mix, new_state = attention_apply(
                p["attn"], h,
                rt=rt,
                mode=attn_mode,
                causal=(kind != ENC_ATTN),
                window=window,
                positions=positions,
                positions3=positions3,
                mrope_sections=b.mrope_sections,
                cache=state,
                cache_len=cache_len,
                q_offset=q_offset,
                kv_len=kv_len,
                q_start=q_start,
                slots=slots,
                block_tables=block_tables,
                want_cache=(mode != "train"),
                qk_norm=b.qk_norm,
                theta=b.rope_theta,
                norm_eps=b.norm_eps,
                sm_scale=b.attention_multiplier,
                use_rope=b.use_rope,
            )
    elif kind == MAMBA2:
        n_true = None
        if mode == "extend" and kv_len is not None:
            start = q_offset if q_start is None else q_start
            n_true = jnp.clip(kv_len - start, 0, x.shape[1])
        if fresh or state is None:
            st = init_block_state(rcfg, kind, x.shape[0], 1, x.dtype)
        elif slots is None:
            st = state
        else:
            st = jax.tree.map(lambda a: a[slots], state)
        impl = rt.attn_impl if rt.attn_impl in (
            "pallas", "pallas_interpret", "naive") else "xla"
        with jax.named_scope("mamba2"):
            mix, rows = ssm.mamba2_apply(
                p["mamba"], h, state=st,
                mode=("step" if mode == "decode" else "full"),
                n_true=n_true, chunk=b.ssm_chunk, impl=impl,
                eps=b.norm_eps)
        if not commit_recurrent:
            new_state = state
        elif slots is None:
            new_state = rows
        else:
            new_state = jax.tree.map(
                lambda a, r: a.at[slots].set(r.astype(a.dtype)), state, rows)
    elif kind == MLSTM:
        assert slots is None and q_start is None, \
            "paged serving (slots) and ragged-start extend (q_start) " \
            "support attention-state models only"
        mix, new_state = ssm.mlstm_apply(
            p["mlstm"], h, state=(None if fresh else state),
            mode=("step" if mode == "decode" else "full"),
            heads=b.num_heads)
    elif kind == SLSTM:
        assert slots is None and q_start is None, \
            "paged serving (slots) and ragged-start extend (q_start) " \
            "support attention-state models only"
        mix, new_state = ssm.slstm_apply(
            p["slstm"], h, state=(None if fresh else state), heads=b.num_heads)
    elif kind == RGLRU:
        assert slots is None and q_start is None, \
            "paged serving (slots) and ragged-start extend (q_start) " \
            "support attention-state models only"
        mix, new_state = ssm.rglru_apply(
            p["rglru"], h, state=(None if fresh else state),
            mode=("step" if mode == "decode" else "full"))
    else:
        raise ValueError(kind)

    rm = b.residual_multiplier
    x = x + (mix if rm == 1.0 else mix * jnp.asarray(rm, mix.dtype))
    if mode == "train":
        new_state = None

    if _has_ffn(rcfg):
        h2 = rmsnorm_apply(p["norm2"], x, b.norm_eps)
        if b.moe is not None:
            strategy = rt.moe_strategy or b.moe.strategy
            y, aux = moe_apply(
                p["moe"], h2, top_k=b.moe.top_k,
                capacity_factor=b.moe.capacity_factor,
                strategy=strategy, act=b.act,
                mesh=rt.mesh, dp_spec=dp_spec)
        else:
            y = mlp_apply(p["mlp"], h2, b.act)
        x = x + (y if rm == 1.0 else y * jnp.asarray(rm, y.dtype))
    return x, new_state, aux
