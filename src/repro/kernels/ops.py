"""Public kernel API: jit'd wrappers that dispatch between implementations.

Implementations
---------------
``pallas``            Mosaic TPU kernel (the deploy target).
``pallas_interpret``  same kernel body, Python interpretation (CPU tests).
``xla``               blocked lax.scan flash attention — used for the
                      CPU AOT dry-run (Mosaic cannot target CPU) and as the
                      large-shape oracle.  FLOP-count matches the kernel:
                      only causally/window-needed (q,kv) block pairs are
                      visited (static pair list), so ``cost_analysis`` on the
                      dry-run reflects real attention work, not a dense S^2.
``naive``             materialized-scores reference (small shapes only).

All functions take q/k/v in [B, S, H, Dh] layout (model-side convention) and
handle the transposition to the kernel layout internally.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from . import sanitize
from .flash_attention import flash_attention_pallas, paged_flash_attention_pallas
from .decode_attention import decode_attention_pallas, paged_decode_attention_pallas
from .relevance_score import relevance_score_pallas
from .ssd_scan import ssd_chunk_scan_pallas

DEFAULT_IMPL = "xla"


def _check_slots(slots, n_rows: int, where: str) -> None:
    """Validate the arena-slot contract when slot values are host-visible.

    Contract: every slot must lie in ``[0, n_rows)`` where ``n_rows`` is
    the arena's row count; the LAST row (index ``n_slots == n_rows - 1``)
    is the serving engine's scratch row and is an explicitly legal
    sentinel that may appear any number of times (batch padding).
    Anything outside that range is a caller bug: the gather path's
    ``jnp.take`` would silently CLIP it to the nearest edge row and the
    paged kernels would DMA an unrelated row — both produce plausible
    garbage rather than an error.  Under ``jit`` the values are traced
    and this check is a no-op (the contract still holds; debug with
    un-jitted calls or ``jax.disable_jit``), so eager callers — tests,
    the un-jitted reference path — fail loudly here instead.
    """
    if isinstance(slots, jax.core.Tracer):
        return
    s = np.asarray(slots)
    if s.size and (int(s.min()) < 0 or int(s.max()) >= n_rows):
        raise ValueError(
            f"{where}: slot ids must be in [0, {n_rows}) — the scratch "
            f"row {n_rows - 1} is the only padding sentinel — got "
            f"min={int(s.min())} max={int(s.max())}")


def _block_granularity(bt: jnp.ndarray, S: int, where: str) -> int:
    """Infer (and validate) the cache-block size a block table addresses.

    A block table is full-width by contract: ``[B, S // block]`` with
    column ``j`` naming the arena row holding positions
    ``[j * block, (j + 1) * block)``.  The granularity is therefore
    recoverable from the table's width — no extra parameter to thread
    through the jitted serving step."""
    if bt.ndim != 2 or bt.shape[1] == 0 or S % bt.shape[1] != 0:
        raise ValueError(
            f"{where}: block table must be [B, S // block] with a width "
            f"dividing the arena cache axis {S}, got shape {bt.shape}")
    return S // bt.shape[1]


def _gather_block_rows(arena: jnp.ndarray, bt: jnp.ndarray,
                       block: int) -> jnp.ndarray:
    """Assemble per-sequence caches [B, S, H, D] from a block table —
    the bitwise reference for the paged kernels' in-kernel indirection
    (block gathers move bits, never recompute them)."""
    N, S, H, D = arena.shape
    nb = S // block
    flat = arena.reshape(N * nb, block, H, D)
    idx = bt.astype(jnp.int32) * nb + jnp.arange(nb, dtype=jnp.int32)[None]
    return jnp.take(flat, idx, axis=0).reshape(bt.shape[0], S, H, D)


# ---------------------------------------------------------------------------
# XLA blocked flash attention (static pair-list scan)
# ---------------------------------------------------------------------------

def _block_pairs(
    nq: int, nk: int, block_q: int, block_kv: int,
    causal: bool, window: Optional[int], q_offset: int,
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Static list of (q_block, kv_block) pairs that contain unmasked work."""
    qi, ki = [], []
    for i in range(nq):
        q_lo = q_offset + i * block_q
        q_hi = q_lo + block_q - 1
        for j in range(nk):
            k_lo = j * block_kv
            k_hi = k_lo + block_kv - 1
            if causal and k_lo > q_hi:
                continue
            if window is not None and window > 0 and k_hi <= q_lo - window:
                # fully left of every row's window in this q block
                continue
            qi.append(i)
            ki.append(j)
    return tuple(qi), tuple(ki)


def _round_up(n: int, block: int) -> int:
    """``n`` rounded up to a whole number of ``block``s."""
    return -(-n // block) * block


def _pad_axis(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    """Zero-pad ``x`` along ``axis`` up to ``size`` (no-op if it holds it):
    ragged extents tile by padding, and the callers slice padded query
    rows off and mask padded keys by ``kv_len``."""
    if x.shape[axis] >= size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "sm_scale", "block_q", "block_kv",
    ),
)
def xla_flash_attention(
    q: jnp.ndarray,               # [B, Sq, Hq, Dh]
    k: jnp.ndarray,               # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,
    kv_len: Optional[jnp.ndarray] = None,   # [B] valid kv length (pad mask)
    q_start: Optional[jnp.ndarray] = None,  # [B] per-row first query pos
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 1024,
) -> jnp.ndarray:
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)

    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk

    if q_start is None:
        qi, ki = _block_pairs(nq, nk, bq, bk, causal, window, q_offset)
        start = q_offset
    else:
        # traced row starts: no block pair is masked for every row
        qi, ki = _block_pairs(nq, nk, bq, bk, False, None, 0)
        start = q_start.astype(jnp.int32)[:, None, None]
    pair_arr = jnp.stack(
        [jnp.asarray(qi, jnp.int32), jnp.asarray(ki, jnp.int32)], axis=1
    )

    qf = q.astype(jnp.float32) * scale

    acc0 = jnp.zeros((B, Sq, Hq, Dh), jnp.float32)
    m0 = jnp.full((B, Sq, Hq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hq), jnp.float32)

    def step(carry, ij):
        acc, m, l = carry
        i, j = ij[0], ij[1]
        qb = jax.lax.dynamic_slice_in_dim(qf, i * bq, bq, axis=1)   # [B,bq,Hq,Dh]
        kb = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)    # [B,bk,Hkv,Dh]
        vb = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
        kb = jnp.repeat(kb.astype(jnp.float32), g, axis=2)
        vb = jnp.repeat(vb.astype(jnp.float32), g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bqhk", qb, kb)                   # [B,bq,Hq,bk]

        qpos = start + i * bq + jnp.arange(bq)[None, :, None]   # [1|B,bq,1]
        kpos = j * bk + jnp.arange(bk)[None, None, :]           # [1, 1, bk]
        mask = jnp.ones((B, bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None and window > 0:
            mask &= kpos > qpos - window
        if kv_len is not None:
            # per-row valid kv length: keys past kv_len[b] are padding
            mask &= kpos < kv_len[:, None, None]
        s = jnp.where(mask[:, :, None, :], s, -jnp.inf)

        mb = jax.lax.dynamic_slice_in_dim(m, i * bq, bq, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(l, i * bq, bq, axis=1)
        ab = jax.lax.dynamic_slice_in_dim(acc, i * bq, bq, axis=1)

        m_cur = jnp.maximum(mb, jnp.max(s, axis=-1))
        # guard: rows with no valid kv yet keep -inf; exp(-inf - -inf) -> nan
        safe_m = jnp.where(jnp.isneginf(m_cur), 0.0, m_cur)
        alpha = jnp.where(jnp.isneginf(mb), 0.0, jnp.exp(mb - safe_m))
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(mask[:, :, None, :], p, 0.0)
        l_cur = lb * alpha + jnp.sum(p, axis=-1)
        a_cur = ab * alpha[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, vb)

        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_cur, i * bq, axis=1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_cur, i * bq, axis=1)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_cur, i * bq, axis=1)
        return (acc, m, l), None

    (acc, _, l), _ = jax.lax.scan(step, (acc0, m0, l0), pair_arr)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Public attention entry points
# ---------------------------------------------------------------------------

def attention(
    q: jnp.ndarray,               # [B, Sq, Hq, Dh]
    k: jnp.ndarray,               # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,   # [B] valid kv length (pad mask)
    q_start: Optional[jnp.ndarray] = None,  # [B] per-row first query pos
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
    block_q: int = 512,
    block_kv: int = 512,
) -> jnp.ndarray:
    """Prefill / prefix-extend attention.

    ``kv_len`` [B] masks per-row KV padding: serving batches are bucket-
    padded, so a document shorter than its bucket carries PAD keys past its
    true length — with ``kv_len`` those keys are invisible to every query
    (the prefill twin of the decode kernel's length mask).

    ``q_start`` [B] places row ``b``'s queries at ``[q_start[b],
    q_start[b] + Sq)`` in place of the static ``q_offset`` (ragged-start
    extend); it is traced, so every start shares one program.  Ragged
    ``Sq``/``Skv`` are padded up to whole blocks on the blocked impls, as
    in ``attention_paged``: extra queries are dropped and extra keys are
    masked.
    """
    if impl == "stub":
        # near-zero-cost stand-in used by the dry-run to ATTRIBUTE HLO
        # flops/bytes to the attention op (delta vs the real lowering);
        # shape/dtype/grad-correct, O(B*S*H*Dh) work.
        g = q.shape[2] // k.shape[2]
        vm = jnp.repeat(jnp.mean(v, axis=1, keepdims=True), g, axis=2)
        return (q * 1e-6 + vm).astype(q.dtype)
    if impl == "naive":
        return ref.mha_reference(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_len=kv_len, q_start=q_start, sm_scale=sm_scale,
        )
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    B, Sq = q.shape[:2]
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    kv_pad = _round_up(Skv, bk)
    q = _pad_axis(q, 1, _round_up(Sq, bq))
    if kv_pad > Skv:
        k = _pad_axis(k, 1, kv_pad)
        v = _pad_axis(v, 1, kv_pad)
        if kv_len is None:
            kv_len = jnp.full((B,), Skv, jnp.int32)
    if impl == "xla":
        out = xla_flash_attention(
            q, k, v, kv_len, q_start, causal=causal, window=window,
            q_offset=q_offset, sm_scale=sm_scale, block_q=bq, block_kv=bk,
        )
        return out[:, :Sq]
    out = flash_attention_pallas(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
        q_start=q_start, sm_scale=sm_scale, block_q=bq, block_kv=bk,
        interpret=(impl == "pallas_interpret"),
    )
    return jnp.swapaxes(out, 1, 2)[:, :Sq]


def decode_attention(
    q: jnp.ndarray,               # [B, Hq, Dh]
    k: jnp.ndarray,               # [B, S, Hkv, Dh]
    v: jnp.ndarray,
    kv_len: jnp.ndarray,          # [B]
    *,
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
    block_kv: int = 512,
) -> jnp.ndarray:
    """Single-token decode attention over a (possibly padded) KV cache."""
    if impl == "stub":
        g = q.shape[1] // k.shape[2]
        vm = jnp.repeat(jnp.mean(v, axis=1), g, axis=1)
        return (q * 1e-6 + vm).astype(q.dtype)
    if impl in ("naive", "xla"):
        return ref.decode_reference(q, k, v, kv_len=kv_len, sm_scale=sm_scale)
    if impl in ("pallas", "pallas_interpret"):
        # Arena allocations round sequence length to the serving bucket plus
        # an operation-suffix reserve, which need not divide block_kv.  Pad
        # the cache axis up to a block multiple here: padded slots sit past
        # every ``kv_len`` so the kernel's scalar-prefetch mask skips them.
        S = k.shape[1]
        bk = min(block_kv, S)
        if S % bk:
            pad = bk - S % bk
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        return decode_attention_pallas(
            q, kt, vt, kv_len, sm_scale=sm_scale, block_kv=block_kv,
            interpret=(impl == "pallas_interpret"),
        )
    raise ValueError(f"unknown decode impl {impl!r}")


def _refuse(where: str, why: str) -> None:
    """A Pallas impl never degrades to the gather/reference path: a shape
    its kernel cannot take is a caller bug (arenas built for a Pallas
    runtime always tile), so say which constraint failed."""
    raise ValueError(
        f"{where}: the Pallas kernel cannot take this shape ({why}); the "
        f"gather fallback exists only for impl='xla'/'naive'")


def arena_decode_attention(
    q: jnp.ndarray,               # [B, Hq, Dh]
    k_arena: jnp.ndarray,         # [N_rows, S, Hkv, Dh] persistent arena
    v_arena: jnp.ndarray,
    slots: jnp.ndarray,           # [B] int32 arena row per sequence
    kv_len: jnp.ndarray,          # [B] valid cache entries per sequence
    *,
    block_tables: Optional[jnp.ndarray] = None,   # [B, S // block] int32
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
    block_kv: int = 512,
) -> jnp.ndarray:
    """Decode attention reading straight from a slot arena — the real
    paged entry point.

    ``block_tables`` [B, S // block] switches the indirection from one
    arena row per sequence to one row per cache block (column ``j`` names
    the row holding positions ``[j * block, (j+1) * block)``), which is
    how many documents share a pinned operation-prefix row.  The table is
    full-width; its granularity is inferred from its shape.  On Pallas
    impls the table rides in scalar-prefetch SMEM and its granularity is
    the kernel's kv block; on ``xla``/``naive`` the blocks are gathered
    into dense per-sequence caches first — a pure bit-move, so both
    planes stay bitwise-identical.

    The serving engine keeps one preallocated KV arena per length bucket
    and addresses sequences by slot id.  On Pallas runtimes the slot
    indices ride in scalar-prefetch SMEM and the kernel's k/v index maps
    DMA ``k_arena[slots[b]]`` blocks in place — no [B, S] gather copy is
    materialized, so per-launch HBM traffic no longer scales with the
    gathered batch.  The arena's cache axis must then be a kv-block
    multiple (Pallas-runtime arenas are); any other shape raises
    ``ValueError``.  ``xla``/``naive`` keep the gather-then-reference
    path as the correctness oracle and CPU plane.

    Slot contract: values must be in ``[0, N_rows)``; the last row
    (``n_slots`` == N_rows - 1) is the scratch row, an explicitly legal
    padding sentinel that may repeat.  Out-of-range ids raise when the
    values are concrete (see ``_check_slots``); under ``jit`` the gather
    path inherits ``jnp.take`` clip semantics and the paged kernel's
    behaviour is undefined — callers own the bound.
    """
    S = k_arena.shape[1]
    pallas = impl in ("pallas", "pallas_interpret")
    if block_tables is not None:
        _check_slots(block_tables, k_arena.shape[0],
                     "arena_decode_attention block_tables")
        sanitize.notify_rows("arena_decode_attention block_tables",
                             block_tables, k_arena.shape[0] - 1)
        tb = _block_granularity(block_tables, S, "arena_decode_attention")
        if pallas:
            # the kernel's kv block IS the table granularity
            return paged_decode_attention_pallas(
                q, k_arena, v_arena, slots, kv_len,
                block_tables=block_tables, sm_scale=sm_scale,
                block_kv=tb, interpret=(impl == "pallas_interpret"))
        k = _gather_block_rows(k_arena, block_tables, tb)
        v = _gather_block_rows(v_arena, block_tables, tb)
        return decode_attention(q, k, v, kv_len, sm_scale=sm_scale,
                                impl=impl, block_kv=block_kv)
    _check_slots(slots, k_arena.shape[0], "arena_decode_attention")
    sanitize.notify_rows("arena_decode_attention", slots,
                         k_arena.shape[0] - 1)
    if pallas:
        if S % min(block_kv, S):
            _refuse("arena_decode_attention",
                    f"cache axis {S} is not a multiple of kv block "
                    f"{block_kv}")
        return paged_decode_attention_pallas(
            q, k_arena, v_arena, slots, kv_len, sm_scale=sm_scale,
            block_kv=block_kv, interpret=(impl == "pallas_interpret"))
    k = jnp.take(k_arena, slots, axis=0)
    v = jnp.take(v_arena, slots, axis=0)
    return decode_attention(q, k, v, kv_len, sm_scale=sm_scale, impl=impl,
                            block_kv=block_kv)


def attention_paged(
    q: jnp.ndarray,               # [B, Sq, Hq, Dh]
    k_arena: jnp.ndarray,         # [N_rows, S_alloc, Hkv, Dh] arena
    v_arena: jnp.ndarray,
    slots: jnp.ndarray,           # [B] int32 arena row per sequence
    *,
    kv_valid: int,                # static: attend keys [0, kv_valid)
    block_tables: Optional[jnp.ndarray] = None,   # [B, S_alloc // block]
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,
    q_start: Optional[jnp.ndarray] = None,  # [B] per-row first query pos
    sm_scale: Optional[float] = None,
    impl: str = DEFAULT_IMPL,
    block_q: int = 512,
    block_kv: int = 512,
) -> jnp.ndarray:
    """Prefix-extend attention over a slot arena (paged extend path).

    ``block_tables`` [B, S_alloc // block] is the per-block indirection
    of ``arena_decode_attention``: shared prefix rows appear in many
    documents' leading columns.  The Pallas kernel consumes the columns
    covering ``kv_valid`` through scalar-prefetch SMEM, with the table
    granularity as its kv block; ``xla``/``naive`` gather blocks into
    dense caches — the same keys either way.

    The paged twin of ``attention`` for the serving engine's extend step:
    queries are the suffix at ``q_offset`` and cached keys live in
    ``k_arena[slots[b], :kv_valid]`` (the caller scatters the new chunk's
    KV into the arena first).  Pallas runtimes resolve slots inside the
    kernel; a ragged ``Sq`` or ``kv_valid`` is padded up to the blocks,
    which needs an arena whose cache axis holds the padded keys (Pallas
    runtimes round it to a block multiple) or the call raises
    ``ValueError``.  ``xla``/``naive`` gather the addressed rows and
    defer to the dense path.  Slot contract as in
    ``arena_decode_attention``.

    ``q_start`` [B] (traced) starts row ``b``'s queries at ``q_start[b]``
    instead of ``q_offset``; ``kv_valid`` must cover ``q_start[b] + Sq``
    for every row.
    """
    S_alloc = k_arena.shape[1]
    rows = slots if block_tables is None else block_tables
    where = "attention_paged" + ("" if block_tables is None
                                 else " block_tables")
    _check_slots(rows, k_arena.shape[0], where)
    sanitize.notify_rows(where, rows, k_arena.shape[0] - 1)
    if impl in ("pallas", "pallas_interpret"):
        Sq = q.shape[1]
        if block_tables is None:
            bk = min(block_kv, kv_valid)
        else:               # the kernel's kv block IS the table granularity
            bk = _block_granularity(block_tables, S_alloc, where)
        bq = min(block_q, Sq)
        # keys in [kv_valid, kv_pad) sit past every row's kv_len, so the
        # mask hides them
        kv_pad = _round_up(kv_valid, bk)
        if kv_pad > S_alloc:
            _refuse(where, f"kv_valid {kv_valid} rounds up to {kv_pad} "
                           f"keys, past the arena's {S_alloc}")
        kv_len = (jnp.full((q.shape[0],), kv_valid, jnp.int32)
                  if kv_len is None else jnp.minimum(kv_len, kv_valid))
        qt = _pad_axis(jnp.swapaxes(q, 1, 2), 2, _round_up(Sq, bq))
        out = paged_flash_attention_pallas(
            qt, k_arena, v_arena, slots, kv_valid=kv_pad,
            block_tables=(None if block_tables is None
                          else block_tables[:, : kv_pad // bk]),
            causal=causal, window=window, q_offset=q_offset,
            kv_len=kv_len, q_start=q_start, sm_scale=sm_scale, block_q=bq,
            block_kv=bk, interpret=(impl == "pallas_interpret"))
        return jnp.swapaxes(out[:, :, :Sq], 1, 2)
    if block_tables is None:
        k = jnp.take(k_arena, slots, axis=0)[:, :kv_valid]
        v = jnp.take(v_arena, slots, axis=0)[:, :kv_valid]
    else:
        tb = _block_granularity(block_tables, S_alloc, where)
        k = _gather_block_rows(k_arena, block_tables, tb)[:, :kv_valid]
        v = _gather_block_rows(v_arena, block_tables, tb)[:, :kv_valid]
    return attention(q, k, v, causal=causal, window=window,
                     q_offset=q_offset, kv_len=kv_len, q_start=q_start,
                     sm_scale=sm_scale, impl=impl, block_q=block_q,
                     block_kv=block_kv)


def relevance_score(
    x: jnp.ndarray,               # [C, T, D]
    lengths: jnp.ndarray,         # [C]
    w: jnp.ndarray,               # [D]
    b: jnp.ndarray,
    *,
    impl: str = DEFAULT_IMPL,
    block_c: Optional[int] = None,      # None: sized to VMEM from T * D
) -> jnp.ndarray:
    if impl in ("naive", "xla"):
        return ref.relevance_reference(x, lengths, w, b)
    if impl in ("pallas", "pallas_interpret"):
        return relevance_score_pallas(
            x, lengths, w, b, block_c=block_c,
            interpret=(impl == "pallas_interpret"),
        )
    raise ValueError(f"unknown relevance impl {impl!r}")


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunk scan
# ---------------------------------------------------------------------------

def _ssd_xla(x, dt, A, Bm, Cm, h0, length, chunk: int):
    """The chunked SSD form of ``ssd_scan`` in jnp, one chunk per
    ``lax.scan`` step (float32 throughout; differentiable)."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    L = chunk
    nc = S // L
    pos = jnp.arange(S)[None, :, None]
    dt = jnp.where(pos < length[:, None, None], dt.astype(f32), 0.0)

    def chunks(a):
        return jnp.moveaxis(a.astype(f32).reshape((B, nc, L) + a.shape[2:]),
                            1, 0)

    tri = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]   # [1,t,s,1]

    def step(h, xs):
        xc, dc, bc, cc = xs          # [B,L,H,P], [B,L,H], [B,L,N], [B,L,N]
        cs = jnp.cumsum(dc * A.astype(f32), axis=1)            # [B,L,H]
        g = jnp.einsum("btn,bsn->bts", cc, bc)
        decay = jnp.exp(jnp.where(tri, cs[:, :, None] - cs[:, None], -jnp.inf))
        m = g[..., None] * decay * dc[:, None]                 # [B,t,s,H]
        y = jnp.einsum("btsh,bshp->bthp", m, xc)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum("btn,bhpn->bthp", cc, h)
        w = jnp.exp(cs[:, -1:] - cs) * dc                      # [B,s,H]
        h = (jnp.exp(cs[:, -1])[..., None, None] * h
             + jnp.einsum("bsh,bshp,bsn->bhpn", w, xc, bc))
        return h, y

    h, ys = jax.lax.scan(step, h0.astype(f32),
                         (chunks(x), chunks(dt), chunks(Bm), chunks(Cm)))
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P).astype(x.dtype), h


def ssd_scan(
    x: jnp.ndarray,               # [B, S, H, P]
    dt: jnp.ndarray,              # [B, S, H] float32, >= 0
    A: jnp.ndarray,               # [H] float32, < 0
    Bm: jnp.ndarray,              # [B, S, N]
    Cm: jnp.ndarray,              # [B, S, N]
    h0: jnp.ndarray,              # [B, H, P, N] float32
    length: jnp.ndarray,          # [B] int32 true tokens of each row
    *,
    chunk: int = 256,
    impl: str = DEFAULT_IMPL,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One Mamba-2 layer's selective scan over a chunk of ``S`` tokens,
    resumed from ``h0``: -> (y [B, S, H, P] in x's dtype, final state
    [B, H, P, N] float32).  Positions at or past ``length[b]`` take dt =
    0, so the final state is the one row ``b``'s last true token left.

    ``pallas``/``pallas_interpret`` run ``ssd_scan.ssd_chunk_scan_pallas``
    (chunks of ``chunk`` tokens; on the chip a shorter ``S`` runs as one
    chunk rounded up to 128 lanes), ``xla`` the same chunked form in
    jnp, ``naive`` the sequential recurrence of ``ref.ssd_reference``.
    A ragged ``S`` is padded up to whole chunks; padded positions lie
    past every length."""
    if impl == "naive":
        y, h = ref.ssd_reference(x, dt, A, Bm, Cm, h0, length)
        return y.astype(x.dtype), h
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown ssd impl {impl!r}")
    S = x.shape[1]
    L = min(chunk, S if impl != "pallas" else _round_up(S, 128))
    Sp = _round_up(S, L)
    x, dt, Bm, Cm = (_pad_axis(a, 1, Sp) for a in (x, dt, Bm, Cm))
    if impl == "xla":
        y, h = _ssd_xla(x, dt, A, Bm, Cm, h0, length, L)
        return y[:, :S], h
    y, h = ssd_chunk_scan_pallas(
        jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2), A, Bm, Cm, h0,
        length, chunk=L, interpret=(impl == "pallas_interpret"))
    return jnp.swapaxes(y, 1, 2)[:, :S], h
