"""Flash-decoding Pallas TPU kernels: one new query token over a KV cache.

Two entry points share one online-softmax body:

``decode_attention_pallas``
    dense layout — q [B, Hq, Dh] (a single token per sequence) over
    k/v [B, Hkv, S, Dh]: row b of the cache belongs to sequence b.

``paged_decode_attention_pallas``
    paged layout — the cache is a persistent slot ARENA
    k/v [N_rows, S, Hkv, Dh] (the serving engine's model-layout state
    pytree, untransposed) and each sequence addresses its row through
    ``slots`` [B].  ``slots`` rides in scalar-prefetch SMEM beside
    ``kv_len`` and the k/v BlockSpec index maps resolve
    ``k_arena[slots[b]]`` *inside* the kernel's DMA schedule, so no
    [B, S] gather copy is ever materialized (vLLM-style paged
    attention).  Any row index in [0, N_rows) is legal — the serving
    arena's scratch row (index ``n_slots`` == N_rows - 1) is an
    explicit sentinel for batch padding and may appear many times.

For GQA every kv head is folded together with its ``g = Hq/Hkv`` grouped
query heads, so the query tile is [g, Dh] (padded to the 8-sublane minimum
by Mosaic automatically).  The dense kernel takes one kv head per grid
step, grid = (B, Hkv, nkv).  The paged kernel cannot: in the arena's
[N_rows, S, Hkv, Dh] layout the head axis is second-minor, and Mosaic only
accepts a block whose last two dims are (8, 128)-divisible or whole, so a
one-head block is refused.  Its k/v block is therefore
``(1, block_kv, Hkv, Dh)`` — every head, one DMA per kv block — and the
kernel loops over the heads with static indices, grid = (B, nkv).

The kv-cache length can exceed the number of valid entries (bucketed cache
allocation); ``kv_len`` [B] masks out unwritten slots.  ``kv_len`` rides in
scalar-prefetch SMEM so the mask costs no extra HBM traffic.

kv is innermost in both grids; f32 accumulators live in VMEM scratch.
Both variants run the identical per-(head, block) math over identical
block contents, so paged and dense outputs agree BITWISE — the serving
engine relies on this to keep paged results exactly equal to the gather
path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_block(q, k, v, kv_len, k0, acc_ref, m_ref, l_ref):
    """Fold one kv block ``k``/``v`` [bkv, dh] into the online-softmax
    state of one head group (query ``q`` [g, dh], already scaled)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [g, bkv]
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < kv_len
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(valid, jnp.exp(s - m_cur[:, None]), 0.0)
    l_ref[:, 0] = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:, 0] = m_cur


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finish(acc_ref, l_ref):
    l = jnp.maximum(l_ref[:, 0], 1e-30)
    return acc_ref[...] / l[:, None]


def _decode_kernel(
    kv_len_ref,                   # SMEM [B] scalar prefetch
    q_ref, k_ref, v_ref,          # VMEM blocks [1, 1, g, dh] / [1, 1, bkv, dh]
    o_ref,
    acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    block_kv: int,
    num_kv_blocks: int,
):
    b = pl.program_id(0)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    kv_len = kv_len_ref[b]
    k0 = jk * block_kv

    @pl.when(k0 < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [g, dh]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bkv, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        _decode_block(q, k, v, kv_len, k0, acc_ref, m_ref, l_ref)

    @pl.when(jk == num_kv_blocks - 1)
    def _done():
        o_ref[0, 0] = _finish(acc_ref, l_ref).astype(o_ref.dtype)


def _paged_decode_kernel(
    rows_ref, kv_len_ref,         # SMEM scalar prefetch (rows feed index maps)
    q_ref, k_ref, v_ref,          # VMEM [1, Hkv, g, dh] / [1, bkv, Hkv, dh]
    o_ref,
    acc_ref, m_ref, l_ref,        # VMEM scratch, leading dim Hkv
    *,
    sm_scale: float,
    block_kv: int,
    num_kv_blocks: int,
    num_kv_heads: int,
):
    b = pl.program_id(0)
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    kv_len = kv_len_ref[b]
    k0 = jk * block_kv

    @pl.when(k0 < kv_len)
    def _compute():
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * sm_scale  # [g, dh]
            k = k_ref[0, :, h, :].astype(jnp.float32)       # [bkv, dh]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            _decode_block(q, k, v, kv_len, k0, acc_ref.at[h], m_ref.at[h],
                          l_ref.at[h])

    @pl.when(jk == num_kv_blocks - 1)
    def _done():
        for h in range(num_kv_heads):
            o_ref[0, h] = _finish(acc_ref.at[h],
                                  l_ref.at[h]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,               # [B, Hq, Dh]
    k: jnp.ndarray,               # [B, Hkv, S, Dh]
    v: jnp.ndarray,
    kv_len: jnp.ndarray,          # [B] int32
    *,
    sm_scale: Optional[float] = None,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, Dh = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)
    block_kv = min(block_kv, S)
    assert S % block_kv == 0, (S, block_kv)
    nkv = S // block_kv

    # [B, Hkv, g, Dh] — grouped query heads per kv head
    qg = q.reshape(B, Hkv, g, Dh)

    kernel = functools.partial(
        _decode_kernel,
        sm_scale=scale,
        block_kv=block_kv,
        num_kv_blocks=nkv,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, Dh), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, Dh), lambda b, h, j, *_: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, Dh), lambda b, h, j, *_: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, Dh), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, Dh), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dh), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, Dh)


def paged_decode_attention_pallas(
    q: jnp.ndarray,               # [B, Hq, Dh]
    k_arena: jnp.ndarray,         # [N_rows, S, Hkv, Dh] persistent arena
    v_arena: jnp.ndarray,
    slots: jnp.ndarray,           # [B] int32 arena row per sequence
    kv_len: jnp.ndarray,          # [B] int32 valid cache entries
    *,
    block_tables: Optional[jnp.ndarray] = None,   # [B, S // block_kv] int32
    sm_scale: Optional[float] = None,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """True paged decode: KV blocks are DMA'd straight from the arena.

    ``slots`` and ``kv_len`` both ride in scalar-prefetch SMEM; the k/v
    index maps address block ``(slots[b], j)``, all heads, of the
    UNGATHERED arena, so per-launch HBM traffic is the addressed blocks
    only — the dense
    path's [B, S] gather copy (``jnp.take``) is eliminated.  The arena
    keeps the model-side [rows, S, Hkv, Dh] layout; only the tiny query
    is reshaped.  ``S`` must be a multiple of the effective kv block (the
    serving arena rounds its per-slot allocation up on Pallas runtimes);
    ``ops.arena_decode_attention`` refuses ragged arenas.

    Slot contract: every value must lie in [0, N_rows); the last arena
    row (``n_slots`` == N_rows - 1) is the serving scratch row and is a
    LEGAL sentinel that may appear repeatedly (batch padding).  Bounds
    are validated host-side in ``ops.arena_decode_attention`` when the
    slot values are concrete.

    ``block_tables`` [B, S // block_kv] generalizes the indirection from
    one row per sequence to one row per CACHE BLOCK: block ``j`` of
    sequence ``b`` is DMA'd from ``(block_tables[b, j], j)``.  The
    within-row block index stays ``j`` — a shared prefix row stores its
    KV at the same positions every consumer reads it at — which is what
    lets many documents' leading blocks point at one pinned prefix row
    (copy-on-write happens at the serving layer by editing the table).
    When given, ``slots`` is ignored by the index maps.
    """
    from . import sanitize        # deferred: keep module import DAG flat
    sanitize.notify_rows(
        "paged_decode_attention_pallas",
        slots if block_tables is None else block_tables,
        k_arena.shape[0] - 1)
    B, Hq, Dh = q.shape
    _, S, Hkv, _ = k_arena.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)
    block_kv = min(block_kv, S)
    assert S % block_kv == 0, (S, block_kv)
    nkv = S // block_kv

    qg = q.reshape(B, Hkv, g, Dh)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=scale,
        block_kv=block_kv,
        num_kv_blocks=nkv,
        num_kv_heads=Hkv,
    )

    if block_tables is None:
        def kv_map(b, j, slots_ref, kv_len_ref):
            return (slots_ref[b], j, 0, 0)
        row_ids = slots.astype(jnp.int32)
    else:
        assert block_tables.shape == (B, nkv), (block_tables.shape, B, nkv)

        def kv_map(b, j, bt_ref, kv_len_ref):
            return (bt_ref[b, j], j, 0, 0)
        row_ids = block_tables.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # (rows, kv_len)
        grid=(B, nkv),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, Dh), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_kv, Hkv, Dh), kv_map),
            pl.BlockSpec((1, block_kv, Hkv, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, Dh), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, Dh), jnp.float32),
            pltpu.VMEM((Hkv, g, 128), jnp.float32),
            pltpu.VMEM((Hkv, g, 128), jnp.float32),
        ],
    )

    # a stable name: the kernel's events in a profiler trace carry it
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dh), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(row_ids, kv_len.astype(jnp.int32), qg, k_arena, v_arena)
    return out.reshape(B, Hq, Dh)
