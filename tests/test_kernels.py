"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Sweeps shapes/dtypes per the deliverable spec; hypothesis drives extra
randomized shape/mask configurations against the reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk_qkv(key, B, Sq, Skv, Hq, Hkv, Dh, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, Hq, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, Skv, Hkv, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, Skv, Hkv, Dh), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,Dh,causal,window,q_off",
    [
        (1, 32, 32, 4, 2, 16, True, None, 0),
        (2, 32, 32, 4, 4, 16, False, None, 0),
        (1, 64, 64, 2, 1, 32, True, 16, 0),     # sliding window
        (1, 16, 64, 4, 2, 16, True, None, 48),   # prefix-extend
        (2, 32, 64, 8, 2, 16, True, 24, 32),     # extend + window
    ],
)
def test_flash_attention_vs_ref(dtype, B, Sq, Skv, Hq, Hkv, Dh, causal,
                                window, q_off):
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, Sq, Skv, Hq, Hkv, Dh, dtype)
    out_ref = ref.mha_reference(q, k, v, causal=causal, window=window,
                                q_offset=q_off)
    out_pal = ops.attention(q, k, v, causal=causal, window=window,
                            q_offset=q_off, impl="pallas_interpret",
                            block_q=16, block_kv=16)
    out_xla = ops.attention(q, k, v, causal=causal, window=window,
                            q_offset=q_off, impl="xla",
                            block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)
    np.testing.assert_allclose(np.asarray(out_xla, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("q_off,causal", [(0, True), (32, True), (0, False)])
def test_flash_attention_per_row_kv_len(q_off, causal):
    """Per-row kv_len masks bucket PAD keys for every query (extend path)."""
    B, Sq, Skv, Hq, Hkv, Dh = 3, 16, 64, 4, 2, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(3), B, Sq, Skv, Hq, Hkv, Dh,
                      jnp.float32)
    kv_len = jnp.asarray([Skv, q_off + 5, 3], jnp.int32)
    out_ref = ref.mha_reference(q, k, v, causal=causal, q_offset=q_off,
                                kv_len=kv_len)
    for impl in ("xla", "pallas_interpret"):
        out = ops.attention(q, k, v, causal=causal, q_offset=q_off,
                            kv_len=kv_len, impl=impl,
                            block_q=16, block_kv=16)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out_ref, np.float32),
                                   atol=ATOL[jnp.float32], rtol=1e-2)
    # row 0 masks nothing: must match the kv_len=None fast path bit-for-bit
    out_none = ops.attention(q, k, v, causal=causal, q_offset=q_off,
                             impl="xla", block_q=16, block_kv=16)
    out_full = ops.attention(q, k, v, causal=causal, q_offset=q_off,
                             kv_len=kv_len, impl="xla",
                             block_q=16, block_kv=16)
    np.testing.assert_array_equal(np.asarray(out_none)[0],
                                  np.asarray(out_full)[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,Dh", [
    (2, 64, 4, 2, 16),
    (1, 128, 8, 1, 32),
    (3, 32, 4, 4, 16),
])
def test_decode_attention_vs_ref(dtype, B, S, Hq, Hkv, Dh):
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32).astype(dtype)
    _, k, v = _mk_qkv(key, B, 1, S, Hq, Hkv, Dh, dtype)
    kv_len = jnp.asarray(
        np.random.default_rng(0).integers(1, S + 1, B), jnp.int32)
    out_ref = ref.decode_reference(q, k, v, kv_len=kv_len)
    out_pal = ops.decode_attention(q, k, v, kv_len,
                                   impl="pallas_interpret", block_kv=16)
    np.testing.assert_allclose(np.asarray(out_pal, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("C,T,D", [(8, 16, 32), (16, 8, 64), (24, 4, 16)])
def test_relevance_score_vs_ref(C, T, D):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (C, T, D), jnp.float32)
    lengths = jnp.asarray(
        np.random.default_rng(1).integers(1, T + 1, C), jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(3), (D,), jnp.float32)
    b = jnp.asarray(0.3, jnp.float32)
    out_ref = ref.relevance_reference(x, lengths, w, b)
    out_pal = ops.relevance_score(x, lengths, w, b,
                                  impl="pallas_interpret", block_c=8)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-5)


def test_relevance_score_ragged_chunk_count():
    """C=130 with block_c=128: internal padding, exact [C] output."""
    C, T, D = 130, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(4), (C, T, D), jnp.float32)
    lengths = jnp.asarray(
        np.random.default_rng(2).integers(1, T + 1, C), jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(5), (D,), jnp.float32)
    b = jnp.asarray(-0.2, jnp.float32)
    out_ref = ref.relevance_reference(x, lengths, w, b)
    out_pal = ops.relevance_score(x, lengths, w, b,
                                  impl="pallas_interpret", block_c=128)
    assert out_pal.shape == (C,)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-5)


def test_decode_attention_ragged_cache_len():
    """S not a block multiple: ops pads the cache axis; kv_len masks pads."""
    B, S, Hq, Hkv, Dh = 2, 72, 4, 2, 16     # 72 % 16 != 0
    q = jax.random.normal(jax.random.PRNGKey(6), (B, Hq, Dh), jnp.float32)
    _, k, v = _mk_qkv(jax.random.PRNGKey(7), B, 1, S, Hq, Hkv, Dh,
                      jnp.float32)
    kv_len = jnp.asarray([40, 72], jnp.int32)
    out_ref = ref.decode_reference(q, k, v, kv_len=kv_len)
    out_pal = ops.decode_attention(q, k, v, kv_len,
                                   impl="pallas_interpret", block_kv=16)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-2)


def test_arena_decode_attention_gathers_slots():
    """Arena layout: rows addressed by slot id match direct decode."""
    N, B, S, Hq, Hkv, Dh = 5, 3, 32, 4, 2, 16
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena = jax.random.normal(jax.random.fold_in(key, 1),
                                (N, S, Hkv, Dh), jnp.float32)
    v_arena = jax.random.normal(jax.random.fold_in(key, 2),
                                (N, S, Hkv, Dh), jnp.float32)
    slots = jnp.asarray([4, 0, 2], jnp.int32)
    kv_len = jnp.asarray([10, 32, 7], jnp.int32)
    out = ops.arena_decode_attention(q, k_arena, v_arena, slots, kv_len,
                                     impl="naive")
    out_ref = ref.decode_reference(
        q, k_arena[np.asarray(slots)], v_arena[np.asarray(slots)],
        kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Paged kernels: in-kernel slot lookup over the arena (no gather copy)
# ---------------------------------------------------------------------------

def _mk_arena(key, N, S, Hkv, Dh):
    k_arena = jax.random.normal(jax.random.fold_in(key, 1),
                                (N, S, Hkv, Dh), jnp.float32)
    v_arena = jax.random.normal(jax.random.fold_in(key, 2),
                                (N, S, Hkv, Dh), jnp.float32)
    return k_arena, v_arena


@pytest.mark.parametrize("slots,kv_len", [
    # permuted, duplicate-free slots; ragged kv_len incl. full and tiny
    ([4, 0, 2], [10, 64, 7]),
    # scratch row (n_slots = N-1) as padding sentinel, duplicated
    ([4, 4, 4], [1, 1, 64]),
    # batch larger than slot count is no constraint either way
    ([3, 1, 0], [64, 33, 16]),
])
def test_paged_decode_bitwise_equals_gather(slots, kv_len):
    """The paged decode kernel (slots in scalar-prefetch SMEM) is BITWISE
    identical to gathering the rows and running the dense kernel — the
    serving engine's paged/gather parity rests on this."""
    N, B, S, Hq, Hkv, Dh = 5, 3, 64, 4, 2, 16   # N not a multiple of B
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S, Hkv, Dh)
    slots = jnp.asarray(slots, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    out_paged = ops.arena_decode_attention(
        q, k_arena, v_arena, slots, kv_len,
        impl="pallas_interpret", block_kv=16)
    out_gather = ops.decode_attention(
        q, k_arena[np.asarray(slots)], v_arena[np.asarray(slots)], kv_len,
        impl="pallas_interpret", block_kv=16)
    np.testing.assert_array_equal(np.asarray(out_paged),
                                  np.asarray(out_gather))
    out_ref = ref.decode_reference(
        q, k_arena[np.asarray(slots)], v_arena[np.asarray(slots)],
        kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out_paged), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-2)


def test_paged_decode_ragged_arena_falls_back_to_gather():
    """S not a kv-block multiple (an arena built for a non-Pallas
    runtime): ``xla`` gathers the rows and runs the reference; a Pallas
    impl refuses the shape instead of silently doing the same."""
    N, B, S, Hq, Hkv, Dh = 4, 2, 72, 4, 2, 16   # 72 % 16 != 0
    key = jax.random.PRNGKey(10)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S, Hkv, Dh)
    slots = jnp.asarray([3, 1], jnp.int32)
    kv_len = jnp.asarray([40, 72], jnp.int32)
    out = ops.arena_decode_attention(q, k_arena, v_arena, slots, kv_len,
                                     impl="xla", block_kv=16)
    out_ref = ref.decode_reference(
        q, k_arena[np.asarray(slots)], v_arena[np.asarray(slots)],
        kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-2)
    with pytest.raises(ValueError, match="cannot take this shape"):
        ops.arena_decode_attention(q, k_arena, v_arena, slots, kv_len,
                                   impl="pallas_interpret", block_kv=16)


@pytest.mark.parametrize("q_off,Sq,kv_valid", [
    (0, 24, 24),       # ragged prefill: Sq and kv_valid pad up to 32
    (16, 24, 40),      # ragged extension past one block
])
def test_paged_extend_pads_ragged_extents(q_off, Sq, kv_valid):
    """A Pallas impl tiles ragged ``Sq``/``kv_valid`` by padding inside
    the arena (extra queries dropped, extra keys masked by kv_len)
    rather than gathering: the result matches the reference."""
    N, B, S_alloc, Hq, Hkv, Dh = 5, 2, 64, 4, 2, 16
    key = jax.random.PRNGKey(14)
    q = jax.random.normal(key, (B, Sq, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    slots = jnp.asarray([4, 1], jnp.int32)
    kv_len = jnp.asarray([kv_valid, q_off + 3], jnp.int32)
    out = ops.attention_paged(
        q, k_arena, v_arena, slots, kv_valid=kv_valid, q_offset=q_off,
        kv_len=kv_len, impl="pallas_interpret", block_q=16, block_kv=16)
    kg = k_arena[np.asarray(slots)][:, :kv_valid]
    vg = v_arena[np.asarray(slots)][:, :kv_valid]
    out_ref = ref.mha_reference(q, kg, vg, causal=True, q_offset=q_off,
                                kv_len=kv_len)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-3)


def test_paged_extend_refuses_keys_past_the_arena():
    """Padding that would read past the arena's cache axis is refused."""
    N, B, S_alloc, Hq, Hkv, Dh = 3, 1, 40, 4, 2, 16    # 40 % 16 != 0
    key = jax.random.PRNGKey(15)
    q = jax.random.normal(key, (B, 8, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    with pytest.raises(ValueError, match="past the arena"):
        ops.attention_paged(q, k_arena, v_arena, jnp.asarray([0]),
                            kv_valid=40, q_offset=32,
                            impl="pallas_interpret", block_q=16,
                            block_kv=16)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("bad", [[-1, 0, 1], [5, 0, 1], [0, 99, 1]])
def test_paged_decode_rejects_out_of_range_slots(impl, bad):
    """Concrete out-of-range slot ids raise instead of clipping silently
    (the jnp.take clip / arbitrary-DMA failure mode of the old gather)."""
    N, B, S, Hq, Hkv, Dh = 5, 3, 32, 4, 2, 16
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S, Hkv, Dh)
    kv_len = jnp.asarray([4, 8, 2], jnp.int32)
    with pytest.raises(ValueError, match="scratch row"):
        ops.arena_decode_attention(q, k_arena, v_arena,
                                   jnp.asarray(bad, jnp.int32), kv_len,
                                   impl=impl, block_kv=16)


@pytest.mark.parametrize("q_off,Sq,kv_valid", [
    (0, 16, 16),       # prefill-into-arena (cached_len == 0)
    (16, 16, 32),      # mid-cascade fraction extension
    (48, 16, 64),      # extension reaching the end of the bucket
])
def test_paged_extend_bitwise_equals_gather(q_off, Sq, kv_valid):
    """Paged flash extend == dense flash on the gathered slice, bitwise,
    with ragged per-row kv_len masking bucket PAD inside the chunk."""
    N, B, S_alloc, Hq, Hkv, Dh = 6, 3, 64, 4, 2, 16
    key = jax.random.PRNGKey(12)
    q = jax.random.normal(key, (B, Sq, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    slots = jnp.asarray([5, 0, 3], jnp.int32)   # scratch row 5 included
    kv_len = jnp.asarray([kv_valid, max(q_off - 3, 1), q_off + 5],
                         jnp.int32)
    out_paged = ops.attention_paged(
        q, k_arena, v_arena, slots, kv_valid=kv_valid, q_offset=q_off,
        kv_len=kv_len, impl="pallas_interpret", block_q=16, block_kv=16)
    kg = k_arena[np.asarray(slots)][:, :kv_valid]
    vg = v_arena[np.asarray(slots)][:, :kv_valid]
    out_dense = ops.attention(
        q, kg, vg, causal=True, q_offset=q_off, kv_len=kv_len,
        impl="pallas_interpret", block_q=16, block_kv=16)
    np.testing.assert_array_equal(np.asarray(out_paged),
                                  np.asarray(out_dense))
    out_ref = ref.mha_reference(q, kg, vg, causal=True, q_offset=q_off,
                                kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out_paged), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-3)


def test_paged_extend_xla_fallback_matches_reference():
    """The gather fallback of ``attention_paged`` (CPU/reference impls)."""
    N, B, S_alloc, Hq, Hkv, Dh = 4, 2, 64, 4, 2, 16
    key = jax.random.PRNGKey(13)
    q = jax.random.normal(key, (B, 16, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    slots = jnp.asarray([2, 3], jnp.int32)
    kv_len = jnp.asarray([30, 17], jnp.int32)
    out = ops.attention_paged(q, k_arena, v_arena, slots, kv_valid=32,
                              q_offset=16, kv_len=kv_len, impl="xla",
                              block_q=16, block_kv=16)
    kg = k_arena[np.asarray(slots)][:, :32]
    vg = v_arena[np.asarray(slots)][:, :32]
    out_ref = ref.mha_reference(q, kg, vg, causal=True, q_offset=16,
                                kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 2),
    nq=st.integers(1, 3),
    nkv=st.integers(1, 3),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2, 4]),
    causal=st.booleans(),
    use_window=st.booleans(),
)
def test_flash_attention_property(b, nq, nkv, hkv, g, causal, use_window):
    """Property sweep: any block-divisible shape matches the oracle."""
    Sq, Skv, Dh = nq * 16, nkv * 16, 8
    window = 24 if use_window else None
    q_off = max(Skv - Sq, 0)
    q, k, v = _mk_qkv(jax.random.PRNGKey(b * 7 + nq), b, Sq, Skv,
                      hkv * g, hkv, Dh, jnp.float32)
    out_ref = ref.mha_reference(q, k, v, causal=causal, window=window,
                                q_offset=q_off)
    out_pal = ops.attention(q, k, v, causal=causal, window=window,
                            q_offset=q_off, impl="pallas_interpret",
                            block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-3)


def test_flash_attention_fully_masked_rows_are_zero():
    """Rows with no visible keys (window slid past) must not NaN."""
    q, k, v = _mk_qkv(jax.random.PRNGKey(5), 1, 32, 32, 2, 1, 16,
                      jnp.float32)
    out = ops.attention(q, k, v, causal=False, window=4, q_offset=64,
                        impl="pallas_interpret", block_q=16, block_kv=16)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

# ---------------------------------------------------------------------------
# Block tables: per-block row indirection (prefix sharing)
# ---------------------------------------------------------------------------

def _materialize(arena, bt, tb):
    """Compose each batch row's virtual cache from its block table:
    positions [j*tb, (j+1)*tb) come from arena row bt[b, j]."""
    bt = np.asarray(bt)
    out = np.stack([
        np.concatenate([np.asarray(arena[bt[b, j], j * tb:(j + 1) * tb])
                        for j in range(bt.shape[1])], axis=0)
        for b in range(bt.shape[0])])
    return jnp.asarray(out)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_paged_decode_block_tables_bitwise(impl):
    """Block-tabled decode == the SAME impl over a materialized arena,
    bitwise: the leading columns point at a shared prefix row, the rest
    at each document's private row (prefix-sharing read geometry)."""
    N, B, S, Hq, Hkv, Dh, tb = 6, 3, 64, 4, 2, 16, 16
    key = jax.random.PRNGKey(21)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S, Hkv, Dh)
    shared = 4                                   # the pinned prefix row
    slots = jnp.asarray([0, 2, 3], jnp.int32)
    bt = np.repeat(np.asarray(slots)[:, None], S // tb, axis=1)
    bt[:, 0] = shared                            # first block shared
    bt = jnp.asarray(bt, jnp.int32)
    kv_len = jnp.asarray([40, 64, 17], jnp.int32)
    out_bt = ops.arena_decode_attention(
        q, k_arena, v_arena, slots, kv_len, block_tables=bt,
        impl=impl, block_kv=tb)
    km = _materialize(k_arena, bt, tb)
    vm = _materialize(v_arena, bt, tb)
    ident = jnp.arange(B, dtype=jnp.int32)
    out_mat = ops.arena_decode_attention(
        q, km, vm, ident, kv_len, impl=impl, block_kv=tb)
    np.testing.assert_array_equal(np.asarray(out_bt), np.asarray(out_mat))


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_paged_extend_block_tables_bitwise(impl):
    """Block-tabled flash extend == the SAME impl over a materialized
    arena, bitwise (mid-cascade fraction extension reading through the
    shared prefix block)."""
    N, B, S_alloc, Hq, Hkv, Dh, tb = 6, 2, 64, 4, 2, 16, 16
    key = jax.random.PRNGKey(22)
    Sq, q_off, kv_valid = 16, 16, 32
    q = jax.random.normal(key, (B, Sq, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    shared = 5
    slots = jnp.asarray([1, 3], jnp.int32)
    bt = np.repeat(np.asarray(slots)[:, None], S_alloc // tb, axis=1)
    bt[:, 0] = shared
    bt = jnp.asarray(bt, jnp.int32)
    kv_len = jnp.asarray([kv_valid, q_off + 7], jnp.int32)
    out_bt = ops.attention_paged(
        q, k_arena, v_arena, slots, kv_valid=kv_valid, q_offset=q_off,
        kv_len=kv_len, block_tables=bt, impl=impl, block_q=tb, block_kv=tb)
    km = _materialize(k_arena, bt, tb)
    vm = _materialize(v_arena, bt, tb)
    ident = jnp.arange(B, dtype=jnp.int32)
    out_mat = ops.attention_paged(
        q, km, vm, ident, kv_valid=kv_valid, q_offset=q_off,
        kv_len=kv_len, impl=impl, block_q=tb, block_kv=tb)
    np.testing.assert_array_equal(np.asarray(out_bt), np.asarray(out_mat))


@pytest.mark.parametrize("kv_valid", [16, 48])
def test_paged_extend_block_tables_coarse_granularity(kv_valid):
    """A table whose block (32) differs from the runtime's kv block (16)
    is read by the Pallas kernel at the table's granularity — kv_valid
    that is not a table-block multiple pads up and masks — and matches
    the gather reference."""
    N, B, S_alloc, Hq, Hkv, Dh, tb = 5, 2, 64, 4, 2, 16, 32
    key = jax.random.PRNGKey(24)
    Sq, q_off = 16, kv_valid - 16
    q = jax.random.normal(key, (B, Sq, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    slots = jnp.asarray([1, 3], jnp.int32)
    bt = np.repeat(np.asarray(slots)[:, None], S_alloc // tb, axis=1)
    bt[:, 0] = 4
    bt = jnp.asarray(bt, jnp.int32)
    kv_len = jnp.asarray([kv_valid, q_off + 5], jnp.int32)
    kw = dict(kv_valid=kv_valid, q_offset=q_off, kv_len=kv_len,
              block_tables=bt, block_q=16, block_kv=16)
    out = ops.attention_paged(q, k_arena, v_arena, slots,
                              impl="pallas_interpret", **kw)
    out_ref = ops.attention_paged(q, k_arena, v_arena, slots, impl="naive",
                                  **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=3e-5, rtol=1e-3)


def test_paged_decode_bf16_arena_tolerance():
    """A bf16-stored arena decodes within quantization tolerance of the
    f32 arena it was cast from (the serving arena's compressed storage)."""
    N, B, S, Hq, Hkv, Dh = 5, 3, 64, 4, 2, 16
    key = jax.random.PRNGKey(23)
    q = jax.random.normal(key, (B, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S, Hkv, Dh)
    slots = jnp.asarray([0, 2, 4], jnp.int32)
    kv_len = jnp.asarray([64, 33, 16], jnp.int32)
    out32 = ops.arena_decode_attention(
        q, k_arena, v_arena, slots, kv_len,
        impl="pallas_interpret", block_kv=16)
    out16 = ops.arena_decode_attention(
        q, k_arena.astype(jnp.bfloat16), v_arena.astype(jnp.bfloat16),
        slots, kv_len, impl="pallas_interpret", block_kv=16)
    np.testing.assert_allclose(np.asarray(out16, np.float32),
                               np.asarray(out32), atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# Ragged-start extend: per-row query starts in scalar-prefetch SMEM
# ---------------------------------------------------------------------------

# (per-row starts, padded extent): each row's queries start at its own true
# length, at most the launch's padded extent; a start below the extent is
# the undershoot case (true fraction under the cached padded one), where
# the chunk's keys overwrite cached positions
_RAGGED_STARTS = [
    ([40, 40, 40], 40),       # every row at the padded extent
    ([40, 13, 0], 40),        # undershoot, and a row starting at 0
    ([7, 31, 22], 40),        # every row below the extent
]


def _ragged_case(starts, ext, Sq):
    N, B, S_alloc, Hq, Hkv, Dh = 6, 3, 80, 4, 2, 16
    key = jax.random.PRNGKey(31)
    q = jax.random.normal(key, (B, Sq, Hq, Dh), jnp.float32)
    k_arena, v_arena = _mk_arena(key, N, S_alloc, Hkv, Dh)
    slots = jnp.asarray([5, 0, 3], jnp.int32)   # scratch row 5 included
    q_start = jnp.asarray(starts, jnp.int32)
    kv_valid = ext + Sq
    kg = k_arena[np.asarray(slots)][:, :kv_valid]
    vg = v_arena[np.asarray(slots)][:, :kv_valid]
    return q, k_arena, v_arena, slots, q_start, kv_valid, kg, vg


@pytest.mark.parametrize("starts,ext", _RAGGED_STARTS)
@pytest.mark.parametrize("Sq", [7, 24])   # within one q block / across two
def test_flash_q_start_vs_ref(starts, ext, Sq):
    """Paged and dense flash with a per-row ``q_start`` (interpret mode),
    and the xla fallback's masked path, match the naive reference."""
    q, k_arena, v_arena, slots, q_start, kv_valid, kg, vg = _ragged_case(
        starts, ext, Sq)
    kv_len = q_start + Sq
    out_ref = ref.mha_reference(q, kg, vg, causal=True, kv_len=kv_len,
                                q_start=q_start)
    outs = {"paged": ops.attention_paged(
        q, k_arena, v_arena, slots, kv_valid=kv_valid, kv_len=kv_len,
        q_start=q_start, impl="pallas_interpret", block_q=16, block_kv=16)}
    for impl in ("pallas_interpret", "xla"):
        outs[impl] = ops.attention(q, kg, vg, causal=True, kv_len=kv_len,
                                   q_start=q_start, impl=impl, block_q=16,
                                   block_kv=16)
    for name, out in outs.items():
        assert out.shape == q.shape, name
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   atol=3e-5, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("starts,ext", _RAGGED_STARTS)
def test_paged_flash_q_start_bitwise_equals_dense(starts, ext):
    """With ``q_start``, paged flash over the arena == dense flash over the
    gathered rows, bitwise; one start for every row == the static
    ``q_offset`` path, bitwise."""
    Sq = 24
    q, k_arena, v_arena, slots, q_start, kv_valid, kg, vg = _ragged_case(
        starts, ext, Sq)
    kv_len = q_start + Sq
    kw = dict(kv_len=kv_len, impl="pallas_interpret", block_q=16,
              block_kv=16)
    out_paged = ops.attention_paged(q, k_arena, v_arena, slots,
                                    kv_valid=kv_valid, q_start=q_start, **kw)
    out_dense = ops.attention(q, kg, vg, causal=True, q_start=q_start, **kw)
    np.testing.assert_array_equal(np.asarray(out_paged),
                                  np.asarray(out_dense))
    if len(set(starts)) == 1:
        out_static = ops.attention_paged(q, k_arena, v_arena, slots,
                                         kv_valid=kv_valid,
                                         q_offset=starts[0], **kw)
        np.testing.assert_array_equal(np.asarray(out_paged),
                                      np.asarray(out_static))


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunk scan
# ---------------------------------------------------------------------------

def _mk_ssd(key, B, S, H, P, N, dtype=jnp.float32):
    k = jax.random.split(key, 6)
    x = jax.random.normal(k[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 2.0)
    A = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(k[3], (B, S, N)).astype(dtype)
    Cm = jax.random.normal(k[4], (B, S, N)).astype(dtype)
    h0 = jax.random.normal(k[5], (B, H, P, N))
    return x, dt, A, Bm, Cm, h0


# chunk 16: S 45 is not a whole number of chunks; a 60-token chunk (the
# served operation) in one; lengths cut rows short of S
@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("S,lengths,chunk", [
    (45, (45, 30), 16), (60, (60, 1), 64), (32, (0, 17), 16)])
def test_ssd_scan_vs_sequential_reference(impl, S, lengths, chunk):
    """The chunked scan resumed from a state, with ragged true lengths,
    equals the float32 one-token recurrence: outputs at true positions
    and the final state (the state a row's last true token left).
    Tolerance 2e-5 abs on values of O(1)-O(10): float32 reassociation of
    the chunk sums, nothing more."""
    x, dt, A, Bm, Cm, h0 = _mk_ssd(jax.random.PRNGKey(3), 2, S, 8, 16, 16)
    length = jnp.asarray(lengths, jnp.int32)
    y_ref, h_ref = ref.ssd_reference(x, dt, A, Bm, Cm, h0, length)
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, h0, length, chunk=chunk,
                        impl=impl)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=2e-5, rtol=2e-5)
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y[b, :n]),
                                   np.asarray(y_ref[b, :n]),
                                   atol=2e-5, rtol=2e-5)


def test_ssd_scan_bf16_operands_within_rounding():
    """bf16 activations (the chip's dtype): the kernel's matrix products
    take bf16 operands with float32 accumulation and a float32 state, so
    it stays within bf16 rounding of the float32 recurrence on the same
    bf16 inputs (2e-2 relative to the largest output)."""
    x, dt, A, Bm, Cm, h0 = _mk_ssd(jax.random.PRNGKey(4), 1, 64, 8, 16, 16,
                                   jnp.bfloat16)
    length = jnp.asarray([64], jnp.int32)
    y_ref, h_ref = ref.ssd_reference(x, dt, A, Bm, Cm, h0, length)
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, h0, length, chunk=32,
                        impl="pallas_interpret")
    scale = float(jnp.max(jnp.abs(y_ref)))
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - y_ref))) \
        < 2e-2 * scale
    assert float(jnp.max(jnp.abs(h - h_ref))) \
        < 2e-2 * float(jnp.max(jnp.abs(h_ref)))
