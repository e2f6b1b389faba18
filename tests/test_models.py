"""Per-arch smoke tests (reduced configs) + serve-path consistency.

Each assigned architecture instantiates a REDUCED same-family config and
runs one forward + one train step on CPU, asserting output shapes and
finiteness.  The cascade primitive (prefill -> extend == full prefill) is
checked for every non-MoE arch (MoE capacity dropping is order-dependent
by design; those assert class-level agreement instead).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import resolve
from repro.configs import ARCHS, get_reduced
from repro.models.model import LM
from repro.models.runtime import CPU_KERNEL_TEST, CPU_TEST, Runtime
from repro.models.whisper import WhisperModel


def make_tiny(arch, **over):
    cfg = get_reduced(arch, dtype="float32", **over)
    rcfg = resolve(cfg, tp=1)
    if cfg.family == "audio":
        return WhisperModel(rcfg, CPU_TEST), cfg
    return LM(rcfg, CPU_TEST), cfg


def tiny_batch(cfg, B=2, S=24, key=0):
    k = jax.random.PRNGKey(key)
    batch = {"tokens": jax.random.randint(k, (B, S), 9, cfg.vocab_size)}
    s_total = S
    if cfg.frontend_stub == "vision_patches":
        batch["patch_emb"] = 0.02 * jax.random.normal(
            k, (B, cfg.frontend_len, cfg.d_model))
        s_total += cfg.frontend_len
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(s_total)[None, :, None], (B, s_total, 3)
        ).astype(jnp.int32)
    if cfg.frontend_stub == "audio_frames":
        batch["frame_emb"] = 0.02 * jax.random.normal(
            k, (B, cfg.encoder_seq_len, cfg.d_model))
    batch["labels"] = jax.random.randint(k, (B, s_total), 9, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    model, cfg = make_tiny(arch)
    params = model.init(jax.random.PRNGKey(0))
    batch = tiny_batch(cfg)
    logits, _ = model.forward(params, batch)
    B, S_total = batch["labels"].shape
    assert logits.shape[0] == B and logits.shape[1] == S_total
    assert logits.shape[2] >= cfg.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits)))

    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if a not in ("whisper_base", "qwen2_vl_2b")])
def test_prefill_extend_matches_full(arch):
    model, cfg = make_tiny(arch)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 9,
                              cfg.vocab_size)
    full_logits, _ = model.prefill(params, {"tokens": toks}, s_alloc=S + 8)
    half = S // 2
    _, st = model.prefill(params, {"tokens": toks[:, :half]}, s_alloc=S + 8)
    ext_logits, _ = model.extend(params, {"tokens": toks[:, half:]}, st,
                                 q_offset=half)
    if cfg.moe is not None:
        # capacity-dropping is batch-order dependent; require argmax match
        assert int(jnp.sum(jnp.argmax(full_logits, -1)
                           != jnp.argmax(ext_logits, -1))) <= B // 2
    else:
        np.testing.assert_allclose(np.asarray(ext_logits),
                                   np.asarray(full_logits),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_runs(arch):
    model, cfg = make_tiny(arch)
    params = model.init(jax.random.PRNGKey(3))
    B, S = 2, 16
    batch = tiny_batch(cfg, B=B, S=S)
    batch.pop("labels")
    if cfg.family == "audio":
        logits, st = model.prefill(params, batch, s_alloc=S + 4)
    else:
        if "positions3" in batch:
            batch.pop("positions3")
            batch.pop("patch_emb")
        logits, st = model.prefill(params, {"tokens": batch["tokens"]},
                                   s_alloc=S + 4)
    nxt = jnp.argmax(logits, -1)
    logits2, st2 = model.decode_step(params, nxt, st,
                                     jnp.full((B,), S, jnp.int32))
    assert logits2.shape == logits.shape
    assert bool(jnp.all(jnp.isfinite(logits2)))


def test_decode_matches_teacher_forcing_dense():
    """Greedy decode logits == teacher-forced forward logits (llama)."""
    model, cfg = make_tiny("llama3_2_1b", num_layers=2)
    params = model.init(jax.random.PRNGKey(4))
    B, S = 1, 12
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + 1), 9,
                              cfg.vocab_size)
    flog, _ = model.forward(params, {"tokens": toks})
    plog, st = model.prefill(params, {"tokens": toks[:, :S]}, s_alloc=S + 4)
    np.testing.assert_allclose(np.asarray(plog), np.asarray(flog[:, S - 1]),
                               atol=2e-5, rtol=1e-4)
    dlog, _ = model.decode_step(params, toks[:, S], st,
                                jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(dlog), np.asarray(flog[:, S]),
                               atol=2e-5, rtol=1e-4)


def test_sliding_window_ring_cache_decode():
    """Local-attention ring cache decode == full-cache reference (gemma3)."""
    model, cfg = make_tiny("gemma3_27b", num_layers=6, sliding_window=8)
    params = model.init(jax.random.PRNGKey(6))
    B, S = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S + 1), 9,
                              cfg.vocab_size)
    flog, _ = model.forward(params, {"tokens": toks})
    _, st = model.prefill(params, {"tokens": toks[:, :S]}, s_alloc=S + 4)
    dlog, _ = model.decode_step(params, toks[:, S], st,
                                jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(dlog), np.asarray(flog[:, S]),
                               atol=3e-5, rtol=1e-3)


def test_mlstm_chunkwise_matches_recurrent():
    from repro.models import ssm
    B, T, H, dh = 2, 32, 2, 8
    key = jax.random.PRNGKey(8)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, T, H, dh))
    k = jax.random.normal(ks[1], (B, T, H, dh))
    v = jax.random.normal(ks[2], (B, T, H, dh))
    li = jax.random.normal(ks[3], (B, T, H))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, T, H)) + 1.0)
    state = ssm.init_mlstm_state(B, H, dh)
    h_seq, st_seq = ssm.mlstm_recurrent_ref(q, k, v, li, lf, state)
    h_chk, st_chk = ssm.mlstm_chunk(q, k, v, li, lf, state, chunk=8)
    np.testing.assert_allclose(np.asarray(h_chk), np.asarray(h_seq),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_chk["C"]),
                               np.asarray(st_seq["C"]), atol=1e-4, rtol=1e-3)


def test_rglru_scan_matches_step_by_step():
    from repro.models import ssm
    d, dr, B, T = 16, 16, 2, 12
    p = ssm.init_rglru(jax.random.PRNGKey(9), d, dr, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(10), (B, T, d))
    y_full, st_full = ssm.rglru_apply(p, x)
    st = None
    ys = []
    for t in range(T):
        y, st = ssm.rglru_apply(p, x[:, t:t + 1], state=st, mode="step")
        ys.append(y)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st["h"]), np.asarray(st_full["h"]),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("paged", [False, True])
def test_one_pass_op_extend_matches_decode_loop(paged):
    """The serving engine's one-pass operation suffix: one ragged-start
    extend of the op tokens at each row's true length gives the logits of
    the per-token decode loop it replaces.  Row 1 starts below the padded
    extent (its op KV overwrites cached document positions), row 2 at it;
    on the paged plane the rows live in a slot arena, scratch row
    included."""
    cfg = get_reduced("llama3_2_1b", dtype="float32", num_layers=2)
    model = LM(resolve(cfg, tp=1), CPU_KERNEL_TEST)
    params = model.init(jax.random.PRNGKey(8))
    B, S, P, s_alloc = 3, 24, 7, 48
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S), 9,
                              cfg.vocab_size)
    op = jax.random.randint(jax.random.PRNGKey(10), (P,), 9, cfg.vocab_size)
    kv_true = jnp.asarray([5, 13, S], jnp.int32)
    if paged:
        slots = jnp.asarray([4, 0, 2], jnp.int32)
        _, st = model.extend(params, {"tokens": toks},
                             model.init_states(5, s_alloc), q_offset=0,
                             slots=slots)
    else:
        slots = None
        _, st = model.prefill(params, {"tokens": toks}, s_alloc=s_alloc)
    one, _ = model.extend(params, {"tokens": jnp.broadcast_to(op, (B, P))},
                          st, q_offset=S, q_start=kv_true, slots=slots)
    loop = None
    for t in range(P):
        loop, st = model.decode_step(params, jnp.broadcast_to(op[t], (B,)),
                                     st, kv_true + t, slots=slots)
    np.testing.assert_allclose(np.asarray(one), np.asarray(loop),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Mamba-2 (granite-4.0-h-micro's recurrent layers)
# ---------------------------------------------------------------------------

def _mamba2_layer(seed=0):
    from repro.models import ssm
    p = ssm.init_mamba2(jax.random.PRNGKey(seed), 64, 4, 16, 16, 4,
                        jnp.float32)
    st = ssm.init_mamba2_state(2, 4, 16, 16, 4, jnp.float32)
    st = jax.tree.map(lambda a: 0.5 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), a.shape), st)     # resume mid-stream
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, 40, 64))
    return ssm, p, st, x


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_mamba2_mixer_matches_recurrence_on_true_tokens(impl):
    """The chunked mixer (chunk 16 over 40 tokens, row 1 true for 23)
    resumed from a state equals the float32 one-token recurrence over
    each row's true tokens: outputs there, and the state (conv window and
    scan state) its last true token left.  2e-5: float32 reassociation."""
    ssm, p, st, x = _mamba2_layer()
    n_true = jnp.asarray([40, 23], jnp.int32)
    y, new = ssm.mamba2_apply(p, x, state=st, n_true=n_true, chunk=16,
                              impl=impl)
    for b, n in enumerate((40, 23)):
        row = jax.tree.map(lambda a: a[b:b + 1], st)
        y_ref, st_ref = ssm.mamba2_recurrent_ref(p, x[b:b + 1, :n], row)
        np.testing.assert_allclose(np.asarray(y[b, :n]),
                                   np.asarray(y_ref[0]), atol=2e-5,
                                   rtol=2e-5)
        for k in ("conv", "ssm"):
            np.testing.assert_allclose(np.asarray(new[k][b]),
                                       np.asarray(st_ref[k][0]),
                                       atol=2e-5, rtol=2e-5)


def test_mamba2_step_after_extend_matches_full_pass():
    """Decode: the one-token step mode after a chunked extend continues
    the full pass exactly (same tolerance and reason as above)."""
    ssm, p, st, x = _mamba2_layer(5)
    y_full, st_full = ssm.mamba2_apply(p, x, state=st, chunk=16)
    _, s = ssm.mamba2_apply(p, x[:, :33], state=st, chunk=16)
    for t in range(33, 40):
        y_t, s = ssm.mamba2_apply(p, x[:, t:t + 1], state=s, mode="step")
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y_full[:, t]), atol=2e-5,
                                   rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s["ssm"]),
                               np.asarray(st_full["ssm"]), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_mamba2_state_across_extends(state_dtype):
    """Two extends (25 + 15 tokens) with the state handed between them
    as the arena holds it match the recurrence over all 40 at the
    tolerance above with a float32 scan state, and fail it with a bf16
    one: the comparison is tight enough to catch the next precision
    down."""
    ssm, p, st, x = _mamba2_layer(7)
    y_ref, st_ref = ssm.mamba2_recurrent_ref(p, x, st)
    y1, mid = ssm.mamba2_apply(p, x[:, :25], state=st, chunk=16)
    mid = dict(mid, ssm=mid["ssm"].astype(state_dtype).astype(jnp.float32))
    y2, end = ssm.mamba2_apply(p, x[:, 25:], state=mid, chunk=16)
    y = np.concatenate([np.asarray(y1), np.asarray(y2)], axis=1)
    close = (np.allclose(y, np.asarray(y_ref), atol=2e-5, rtol=2e-5)
             and np.allclose(np.asarray(end["ssm"]),
                             np.asarray(st_ref["ssm"]), atol=2e-5,
                             rtol=2e-5))
    assert close == (state_dtype == jnp.float32)
