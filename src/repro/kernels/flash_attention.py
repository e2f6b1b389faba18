"""Blocked flash attention Pallas TPU kernel with prefix-extend semantics.

Supports:
  * causal and bidirectional attention,
  * sliding-window masking (Gemma3 / RecurrentGemma local layers),
  * GQA (q heads grouped over kv heads),
  * ``q_offset`` — queries are the *suffix* of a longer sequence whose first
    ``q_offset`` tokens already live in the KV operand.  This is the task-
    cascade primitive: extending a document from fraction f_j to f_i > f_j
    re-uses the cached prefix KV and only computes attention for new queries.
  * ``q_start`` [B] — a per-row first query position in place of the static
    ``q_offset`` (ragged-start extend: the serving engine's operation chunk
    begins at each document's own true length).  It rides in scalar-prefetch
    SMEM, so one compiled kernel serves every start; the static offset is
    the case ``q_start == q_offset`` for every row.
  * ``kv_len`` [B] — per-row valid KV length (bucket-padded serving batches:
    keys at positions >= kv_len[b] are PAD and masked for every query).
    Rides in scalar-prefetch SMEM like the decode kernel's length mask.

Layout: q [B, Hq, Sq, Dh]; k/v [B, Hkv, Skv, Dh] (callers transpose from
[B, S, H, Dh]).  Grid = (B, Hq, nq, nkv) with the kv dimension innermost;
the output block index is constant over the kv dimension, so the f32
accumulator / running max / running denominator live in VMEM scratch across
kv iterations (the canonical TPU "revisiting" pattern).

``paged_flash_attention_pallas`` is the slot-addressed twin for the
serving engine's extend path: k/v come from a persistent arena
[N_rows, S_alloc, Hkv, Dh] (model layout, untransposed) and each batch row
resolves its arena row through ``slots`` [B] riding in scalar-prefetch
SMEM beside ``kv_len`` — the k/v index maps DMA ``k_arena[slots[b]]``
blocks directly, so a mid-cascade re-entry prefill appends into the arena
without first gathering a [B, S] copy.  The arena's head axis is
second-minor, and Mosaic refuses a block of one head there (a block's last
two dims must be (8, 128)-divisible or whole), so each k/v block holds
every head, ``(1, block_kv, Hkv, Dh)``, is DMA'd once, and serves all
``Hq`` query heads: grid = (B, nq, nkv) with a static loop over heads and
per-head accumulators.  Per-(head, block) math is identical to the dense
kernel, so paged and gather outputs agree bitwise.

Block shapes must tile the sequence lengths; ``ops.attention`` picks
hardware-aligned blocks (multiples of 8 sublanes x 128 lanes; MXU-friendly
head_dim 128/256) and asserts divisibility.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import NEG_INF, _finish, _init_state


def _flash_block(q, k, v, kv_len, q0, k0, acc_ref, m_ref, l_ref, *,
                 causal: bool, window: Optional[int], block_q: int,
                 block_kv: int):
    """Fold one kv block ``k``/``v`` [bkv, dh] into one query head's
    online-softmax state (query block ``q`` [bq, dh], already scaled)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # [bq, bkv]

    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window is not None and window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                                 # [bq]
    l_prev = l_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:, 0] = m_cur
    l_ref[:, 0] = l_cur


def _block_runs(q0, k0, kv_len, *, causal, window, block_q, block_kv):
    """Block-level pruning: False when every (query, key) pair of the
    block is masked."""
    run = k0 < kv_len
    if causal:
        run &= k0 <= q0 + block_q - 1
    if window is not None and window > 0:
        run &= (k0 + block_kv - 1) > (q0 - window)
    return run


def _flash_kernel(
    kv_len_ref, q_start_ref,      # SMEM [B] scalar prefetch
    q_ref, k_ref, v_ref,          # VMEM blocks
    o_ref,                        # output block
    acc_ref, m_ref, l_ref,        # VMEM scratch (persist across kv steps)
    *,
    sm_scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # absolute positions of this block's first query / key
    q0 = q_start_ref[b] + iq * block_q
    k0 = ik * block_kv
    kv_len = kv_len_ref[b]
    blk = dict(causal=causal, window=window, block_q=block_q,
               block_kv=block_kv)

    @pl.when(_block_runs(q0, k0, kv_len, **blk))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bkv, dh]
        v = v_ref[0, 0].astype(jnp.float32)                 # [bkv, dh]
        _flash_block(q, k, v, kv_len, q0, k0, acc_ref, m_ref, l_ref, **blk)

    @pl.when(ik == num_kv_blocks - 1)
    def _done():
        o_ref[0, 0] = _finish(acc_ref, l_ref).astype(o_ref.dtype)


def _paged_flash_kernel(
    rows_ref, kv_len_ref, q_start_ref,   # SMEM scalar prefetch (rows feed
    #                                      the k/v index maps)
    q_ref, k_ref, v_ref,          # VMEM [1, Hq, bq, dh] / [1, bkv, Hkv, dh]
    o_ref,                        # [1, Hq, bq, dh]
    acc_ref, m_ref, l_ref,        # VMEM scratch, leading dim Hq
    *,
    sm_scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    num_kv_heads: int,
    group: int,
):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    q0 = q_start_ref[b] + iq * block_q
    k0 = ik * block_kv
    kv_len = kv_len_ref[b]
    blk = dict(causal=causal, window=window, block_q=block_q,
               block_kv=block_kv)

    @pl.when(_block_runs(q0, k0, kv_len, **blk))
    def _compute():
        for hk in range(num_kv_heads):
            k = k_ref[0, :, hk, :].astype(jnp.float32)     # [bkv, dh]
            v = v_ref[0, :, hk, :].astype(jnp.float32)
            for h in range(hk * group, (hk + 1) * group):
                q = q_ref[0, h].astype(jnp.float32) * sm_scale
                _flash_block(q, k, v, kv_len, q0, k0, acc_ref.at[h],
                             m_ref.at[h], l_ref.at[h], **blk)

    @pl.when(ik == num_kv_blocks - 1)
    def _done():
        for h in range(num_kv_heads * group):
            o_ref[0, h] = _finish(acc_ref.at[h],
                                  l_ref.at[h]).astype(o_ref.dtype)


def _paged_flash_vmem_bytes(hq: int, block_q: int, block_kv: int, hkv: int,
                            dh: int, q_bytes: int, kv_bytes: int) -> int:
    """VMEM working set of one paged flash grid step, counted at Mosaic's
    tiling (lanes pad to 128, sublanes to 8 x 4-byte words): double-
    buffered q/out and k/v blocks, the per-head f32 accumulators, and
    the [bq, bkv] score temporaries."""
    lanes = -(-dh // 128) * 128
    sub = 8 * 4 // kv_bytes
    kv_rows = -(-hkv // sub) * sub
    qo = 2 * 2 * hq * block_q * lanes * q_bytes
    kv = 2 * 2 * block_kv * kv_rows * lanes * kv_bytes
    scratch = hq * block_q * (lanes + 2 * 128) * 4
    temps = 4 * block_q * max(block_kv, 128) * 4
    return qo + kv + scratch + temps


def flash_attention_pallas(
    q: jnp.ndarray,               # [B, Hq, Sq, Dh]
    k: jnp.ndarray,               # [B, Hkv, Skv, Dh]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,   # [B] valid kv length (pad mask)
    q_start: Optional[jnp.ndarray] = None,  # [B] first query position
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """``q_start`` [B], when given, replaces ``q_offset`` row by row."""
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0, (Sq, block_q)
    assert Skv % block_kv == 0, (Skv, block_kv)
    nq = Sq // block_q
    nkv = Skv // block_kv

    if kv_len is None:
        kv_len = jnp.full((B,), Skv, jnp.int32)   # every key valid
    if q_start is None:
        q_start = jnp.full((B,), q_offset, jnp.int32)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nkv,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # (kv_len, q_start)
        grid=(B, Hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, Dh),
                         lambda b, h, i, j, *_: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, Dh),
                         lambda b, h, i, j, *_: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dh), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dh), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q_start.astype(jnp.int32), q, k, v)


def paged_flash_attention_pallas(
    q: jnp.ndarray,               # [B, Hq, Sq, Dh]
    k_arena: jnp.ndarray,         # [N_rows, S_alloc, Hkv, Dh] arena
    v_arena: jnp.ndarray,
    slots: jnp.ndarray,           # [B] int32 arena row per sequence
    *,
    kv_valid: int,                # static: attend keys [0, kv_valid)
    block_tables: Optional[jnp.ndarray] = None,   # [B, nkv] int32
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,   # [B] valid kv length (pad mask)
    q_start: Optional[jnp.ndarray] = None,  # [B] first query position
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Prefix-extend attention reading K/V straight from a slot arena.

    The queries are the suffix [q_offset, q_offset + Sq) of each
    sequence, or [q_start[b], q_start[b] + Sq) of row ``b`` when
    ``q_start`` is given (the block pruning and causal mask then follow
    each row; ``kv_valid`` must cover every row's last query); cached
    keys live in ``k_arena[slots[b], :kv_valid]`` (chunk included — the
    caller scatters the new chunk's KV into the arena BEFORE attending,
    mirroring the dense extend path).  Only the
    kv blocks covering ``kv_valid`` are visited, so the arena's op-suffix
    reserve past the bucket costs nothing.  Slot contract as in
    ``paged_decode_attention_pallas``: any row in [0, N_rows) is legal,
    the scratch row (N_rows - 1) explicitly so, duplicates allowed.

    ``block_tables`` [B, kv_valid // block_kv] switches the
    indirection to per-block granularity: kv block ``j`` of row ``b`` is
    DMA'd from ``(block_tables[b, j], j)`` — the within-row
    index stays ``j``, so shared prefix rows are read at the positions
    they were prefilled at.  When given, ``slots`` is ignored.
    """
    from . import sanitize        # deferred: keep module import DAG flat
    sanitize.notify_rows(
        "paged_flash_attention_pallas",
        slots if block_tables is None else block_tables,
        k_arena.shape[0] - 1)
    B, Hq, Sq, Dh = q.shape
    _, S_alloc, Hkv, _ = k_arena.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    assert 0 < kv_valid <= S_alloc, (kv_valid, S_alloc)
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, kv_valid)
    assert Sq % block_q == 0, (Sq, block_q)
    assert kv_valid % block_kv == 0, (kv_valid, block_kv)
    nq = Sq // block_q
    nkv = kv_valid // block_kv

    if kv_len is None:
        kv_len = jnp.full((B,), kv_valid, jnp.int32)
    if q_start is None:
        q_start = jnp.full((B,), q_offset, jnp.int32)

    kernel = functools.partial(
        _paged_flash_kernel,
        sm_scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nkv,
        num_kv_heads=Hkv,
        group=g,
    )

    if block_tables is None:
        def kv_map(b, i, j, slots_ref, *_):
            return (slots_ref[b], j, 0, 0)
        row_ids = slots.astype(jnp.int32)
    else:
        assert block_tables.shape == (B, nkv), (block_tables.shape, B, nkv)

        def kv_map(b, i, j, bt_ref, *_):
            return (bt_ref[b, j], j, 0, 0)
        row_ids = block_tables.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # (rows, kv_len, q_start)
        grid=(B, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, Hq, block_q, Dh),
                         lambda b, i, j, *_: (b, 0, i, 0)),
            pl.BlockSpec((1, block_kv, Hkv, Dh), kv_map),
            pl.BlockSpec((1, block_kv, Hkv, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, block_q, Dh),
                               lambda b, i, j, *_: (b, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, block_q, Dh), jnp.float32),
            pltpu.VMEM((Hq, block_q, 128), jnp.float32),
            pltpu.VMEM((Hq, block_q, 128), jnp.float32),
        ],
    )
    # every query head's accumulators stay live across the kv loop, so the
    # working set grows with Hq * block_q: raise Mosaic's scoped-VMEM limit
    # to fit it (v5e has 128 MiB of VMEM; the default scope is 16 MiB)
    need = _paged_flash_vmem_bytes(Hq, block_q, block_kv, Hkv, Dh,
                                   q.dtype.itemsize, k_arena.dtype.itemsize)
    params = pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need * 5 // 4, 32 << 20), 100 << 20)))

    # a stable name: the kernel's events in a profiler trace carry it
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dh), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_flash_attention",
    )(row_ids, kv_len.astype(jnp.int32), q_start.astype(jnp.int32), q,
      k_arena, v_arena)
