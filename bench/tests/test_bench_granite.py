"""The granite-4.0-h-micro oracle (bench/arch/granitemoehybrid.py) against
the program, on seeded random weights at a small granite-shaped size on
the CPU; its work counts against hand formulas at the published sizes;
and the position rule of the kernel readers on a hand-built trace in
which 40 Pallas kernels make a pass and the flash kernels sit at 5, 15,
25 and 35."""
import json
import math
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import check as CK  # noqa: E402
import harness  # noqa: E402
import reference as REF  # noqa: E402
import weights as W  # noqa: E402
import test_bench_spans as SP  # noqa: E402

from repro.config import resolve  # noqa: E402
from repro.data.tokenizer import HashWordTokenizer  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.models.runtime import Runtime  # noqa: E402
from repro.serving.engine import LMBackend  # noqa: E402

GRANITE = harness.load_arch(ROOT, "granitemoehybrid")
CONFIG = json.load(open(os.path.join(
    BENCH, "hybrid_configs", "qwen3-1.7b_granite-4.0-h-micro.json")))
ORACLE = CONFIG["models"]["oracle"]
SMALL = {"name": "g", "model_type": "granitemoehybrid", "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 128, "shared_intermediate_size": 128,
         "vocab_size": 512, "num_hidden_layers": 8,
         "layer_types": ["mamba", "attention", "mamba", "mamba"] * 2,
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
         "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
         "mamba_chunk_size": 16, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False,
         "attention_multiplier": 0.0625, "embedding_multiplier": 12,
         "residual_multiplier": 0.22, "logits_scaling": 8,
         "position_embedding_type": "nope", "rms_norm_eps": 1e-5,
         "tie_word_embeddings": True, "hidden_act": "silu",
         "num_local_experts": 0, "max_position_embeddings": 4096}
RT = Runtime(attn_impl="pallas_interpret", block_q=16, block_kv=16,
             remat=False)


def _f32_model(m=SMALL, seed=3):
    """The served tree, cast to float32, behind a float32 model: program
    and reference then differ by float32 reassociation alone."""
    lm = LM(resolve(GRANITE.model_config(m, "float32"), tp=1), RT)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     GRANITE.make_params(m, seed, 1))
    W.check_layout(p, jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    return lm, p


def _ref_logits(p, tokens, n_valid, m=SMALL):
    """The reference's logits over the whole vocabulary after token
    ``n_valid - 1``."""
    fwd = GRANITE._compiled(json.dumps(m, sort_keys=True), False)
    S = -(-len(tokens) // 16) * 16
    toks = np.zeros(S, np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(p, jnp.asarray(toks), jnp.int32(n_valid),
                              jnp.arange(m["vocab_size"])))


def test_make_params_match_the_model_tree_at_published_sizes():
    """The weights' tree, shapes and dtypes are the serving model's own,
    at the published widths (shapes only: nothing is made)."""
    lm = LM(resolve(GRANITE.model_config(ORACLE, "bfloat16"), tp=1), RT)
    made = jax.eval_shape(lambda: GRANITE._make(ORACLE, jnp.uint32(1),
                                                jnp.uint32(0), role=1))
    W.check_layout(made, jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(made))
    assert n == 3_191_396_096


def test_prefill_then_decode_matches_the_reference():
    """The program's prefill (chunked scan, paged kernels' dense twin)
    and then decode through the states (one-token recurrence, decode
    attention) agree with the reference's full forward pass on every
    logit after each token.  Tolerance 2e-5 of the largest logit:
    float32 reassociation between the chunked and the sequential scan
    and between the attention kernels and the dense softmax."""
    lm, p = _f32_model()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (40,), 16,
                                         512))
    logits, st = lm.prefill(p, {"tokens": jnp.asarray(toks[None, :33])},
                            s_alloc=48)
    for t in range(33, 40):
        ref = _ref_logits(p, toks, t)
        tol = 2e-5 * np.abs(ref).max()
        np.testing.assert_allclose(np.asarray(logits[0]), ref, atol=tol,
                                   rtol=0)
        logits, st = lm.decode_step(p, jnp.asarray(toks[t:t + 1]), st,
                                    jnp.asarray([t], jnp.int32))


def test_paged_one_pass_op_suffix_matches_class_logits():
    """The engine on the paged plane, the operation as one ragged-start
    extend, answers each document as the reference's ``class_logits``
    does: the check's own gap under 1e-4 nats (float32 reassociation)."""
    lm, p = _f32_model()
    tokz = HashWordTokenizer(vocab_size=512)
    be = LMBackend(name="oracle", model=lm, params=p, tokenizer=tokz,
                   paged=True)
    assert be.uses_paged_kv() and be.one_pass_op_suffix
    texts = {d: " ".join(f"w{d}x{j}" for j in range(n))
             for d, n in enumerate((50, 37, 64))}
    toks = {d: np.asarray(tokz.encode(t), np.int32) for d, t in texts.items()}
    op_text = "does this overturn a lower court decision"
    op = np.asarray(tokz.encode(op_text), np.int32)
    pred, conf, *_ = be.run_stage(list(texts), toks, 64, 1.0, op, 6)
    for i, text in texts.items():
        prompt = REF.tokenize(text, 512) + REF.tokenize(op_text, 512)
        ref = GRANITE.class_logits(p, SMALL, prompt, 6)
        assert CK.answer_gap(ref, int(pred[i]), float(conf[i])) < 1e-4


def test_control_rounds_matrices_only():
    """The control is the reference with every matrix rounded to fp8 (per
    output channel): the plain reference on a tree whose matrices were
    rounded by hand lands within a quarter of the control's distance
    from the plain logits (not exactly on it: inside the jitted pass XLA
    may divide by the scale differently, and one flipped fp8 rounding
    moves a weight by an eighth)."""
    _, p = _f32_model()
    prompt = list(range(16, 60))
    axes = {"in_proj": (1,), "out_proj": (1,), "wq": (1,), "wk": (1,),
            "wv": (1,), "wo": (1, 2), "w1": (1,), "w2": (1,), "w3": (1,)}

    def rounded(path, a):
        name = jax.tree_util.keystr(path).split("'")[-2]
        return REF.fp8_round(a, axes[name]) if name in axes else a
    hand = dict(p, embed={"table": REF.fp8_round(p["embed"]["table"], (1,))},
                stages=jax.tree_util.tree_map_with_path(rounded,
                                                        p["stages"]))
    fp8 = GRANITE.class_logits(p, SMALL, prompt, 6, control=True)
    moved = np.abs(GRANITE.class_logits(p, SMALL, prompt, 6) - fp8).max()
    assert moved > 1e-4
    assert np.abs(GRANITE.class_logits(hand, SMALL, prompt, 6)
                  - fp8).max() < 0.25 * moved


def test_stage_work_against_hand_formulas():
    """At the published sizes: linear FLOPs are twice the model's matrix
    parameters a token (every in/out projection, q, k, v, o and SwiGLU
    weight of the model's own tree, the embedding and the conv left out);
    the scan is 5 H P N a token on 36 layers; the recurrent state is what
    the engine's arena row holds of it; attention runs on 4 layers."""
    m = ORACLE
    lm = LM(resolve(GRANITE.model_config(m, "bfloat16"), tp=1), RT)
    tree = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    matrices = sum(math.prod(a.shape) for k, a in
                   jax.tree_util.tree_flatten_with_path(tree)[0]
                   if jax.tree_util.keystr(k).split("'")[-2] in
                   ("in_proj", "out_proj", "wq", "wk", "wv", "wo",
                    "w1", "w2", "w3"))
    w = GRANITE.stage_work(m, 100, 3100, 60, 6)
    tokens = 3100 + 60 - 100
    assert w["tokens"] == tokens
    assert w["linear_flops"] == 2 * matrices * tokens
    assert w["scan_flops"] == 36 * 5 * 64 * 64 * 128 * tokens
    per_tok = 2 * (2 * 4096 + 2 * 128) + 4 * 64
    assert w["scan_bytes"] == 36 * (per_tok * tokens + 8 * 64 * 64 * 128)
    be = LMBackend(name="oracle", model=lm, params=None,
                   tokenizer=HashWordTokenizer(512))
    assert GRANITE.recurrent_state_bytes(m) == \
        be.row_nbytes(4096)["recurrent"] == 76_437_504
    assert w["state_bytes"] == 2 * 76_437_504           # read, then write
    assert GRANITE.stage_work(m, 0, 3100, 60, 6)["state_bytes"] == \
        76_437_504                                       # written once
    keys = 3160 * 3161 // 2 - 100 * 101 // 2
    assert w["attn_flops"] == 4 * 4 * 32 * 64 * keys
    assert w["flops"] == (w["linear_flops"] + w["attn_flops"]
                          + w["scan_flops"] + w["head_flops"])
    assert GRANITE.pallas_kernels(m).count("flash") == 4
    assert [i for i, k in enumerate(GRANITE.pallas_kernels(m))
            if k == "flash"] == [5, 15, 25, 35]
    assert GRANITE.paged_attention_layers(m) == 40


def test_config_holds_the_catalog_entry():
    """The file keeps the catalog's numbers under the catalog's keys, and
    the entry the program runs holds the same; nothing is cut."""
    assert CONFIG["reduced"] == []
    for k, v in ORACLE.items():
        if k not in ("name", "source", "torch_dtype"):
            assert CONFIG[k] == v, k


# ------------------------------------------- the kernel position rule
def _kernels(start, kinds):
    """One kernel every 3 ms from ``start``: a scan lasts 1 ms, a flash
    kernel 2 ms, so a rule that takes the wrong positions sums the wrong
    time."""
    return [(start + 3 * i, start + 3 * i + (1 if k == "scan" else 2))
            for i, k in enumerate(kinds)]


def _hybrid_run(drop_one=False):
    kinds = GRANITE.pallas_kernels(ORACLE)               # 40, flash at 5..
    programs = [("jit_paged_step", 12, 18),              # window's first
                ("jit_paged_step", 20, 270),             # oracle, new tokens
                ("jit_paged_step", 280, 400),            # oracle, decode-only
                ("jit_paged_step", 410, 430)]            # proxy, 2 layers
    kernels = ([(13, 14)] + _kernels(21, kinds * 2) + _kernels(281, kinds)
               + [(411, 412), (414, 415), (417, 418), (420, 421)])
    if drop_one:
        kernels.remove(_kernels(21, kinds * 2)[60])
    recs = [SP._rec(0, "proxy", 11, 19), SP._rec(1, "oracle", 19.5, 271),
            SP._rec(2, "oracle", 275, 401, decode_only=True),
            SP._rec(3, "proxy", 405, 431)]
    required = {"scan_flops": int(197e12 * 0.010), "scan_bytes": 0}
    log = [harness.Launch(model=r.model, t_enqueue=r.ts_enqueue,
                          required=dict(required), computed_tokens=0)
           for r in recs]
    from jax.profiler import ProfileData
    import trace_reduce as TRR
    cell = SimpleNamespace(
        config={"models": {"proxy": {"num_hidden_layers": 2},
                           "oracle": ORACLE}},
        arch={"proxy": harness.load_arch(ROOT, "qwen3"), "oracle": GRANITE})
    return harness.Run(
        cell=cell, seconds=1.0, t_open=SP.T0, t_close=SP.T0 + 1,
        setup_s=0.0, served=[], launches=recs, launch_log=log,
        trace=TRR.reduce_profile(ProfileData.from_text_proto(
            SP._trace(programs, kernels, window=(10, 500)))),
        trace_span=(SP.T0, SP.T0 + 0.49),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _read(metric, run):
    return harness.load_reader(ROOT, metric)(run)


def test_kernel_readers_pick_the_hybrid_kernels_by_position():
    """The op suffix starts at the 41st kernel of a launch with new
    tokens (the first of the op pass), at the first of a decode-only one;
    the scan kernels are the 72 (36 decode-only) off the flash
    positions; the window's first program is left out."""
    run = _hybrid_run()
    # op suffix: 141-270 of 20-270, 281-400 of 280-400, 417-430 of 410-430
    assert _read("op_suffix_share.column", run) == \
        pytest.approx(100.0 * (129 + 119 + 13) / (250 + 120 + 20))
    assert _read("ssd_share.column", run) == \
        pytest.approx(100.0 * (72 + 36) / (250 + 120))
    # 10 ms of required FLOPs a launch, two launches, 108 ms of scans
    assert _read("ssd_roofline.column", run) == \
        pytest.approx(100.0 * 0.020 / 0.108)


def test_kernel_readers_read_none_when_the_rule_breaks():
    """A program that lost a kernel no longer fits the rule: the scan
    readers read None rather than a sum over shifted positions."""
    run = _hybrid_run(drop_one=True)
    assert _read("ssd_share.column", run) is None
    assert _read("ssd_roofline.column", run) is None


def test_state_bytes_per_doc_reads_the_launch_records():
    run = _hybrid_run()
    assert _read("state_bytes_per_doc.column", run) is None   # no docs
    for r in run.launches:
        r.state_bytes = 3_000_000
    run.served = [harness.Served(doc=None, t_due=0, t_submit=0,
                                 t_done=SP.T0 + 0.5, status="resolved")] * 2
    assert _read("state_bytes_per_doc.column", run) == pytest.approx(6.0)


# ------------------------------------------------ a whole run on the CPU
@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """A checkout whose one cell pairs a small Qwen3 proxy with a small
    granite oracle on the paged plane (Pallas kernels interpreted)."""
    import shutil
    r = tmp_path_factory.mktemp("hybridroot")
    (r / "bench" / "configs").mkdir(parents=True)
    (r / "bench" / "traffic").mkdir()
    for d in ("arch", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), r / "bench" / d)
    proxy = {"name": "p", "model_type": "qwen3", "hidden_size": 64,
             "intermediate_size": 128, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "vocab_size": 512,
             "max_position_embeddings": 4096, "rope_theta": 10000,
             "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
             "hidden_act": "silu"}
    cfg = {"name": "hyb", "reduced": [], "dtype": "bfloat16",
           "models": {"proxy": proxy, "oracle": SMALL},
           "serving": {"batch": 2, "inflight": 1,
                       "attn_impl": "pallas_interpret", "block_q": 16,
                       "block_kv": 16, "op_reserve": 8,
                       "arena_slots": {"proxy": 3, "oracle": 4}}}
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "pubmed-column.json")))
    mix.update(length={"median": 24, "sigma": 0.3, "min": 17, "max": 32},
               operations={"o_orig": 6}, block=10, window_docs_per_s=10.0,
               sample=2, limits={"class_logprob_gap": 0.05})
    (r / "bench" / "configs" / "hyb.json").write_text(json.dumps(cfg))
    (r / "bench" / "traffic" / "hyb.json").write_text(json.dumps(mix))
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["configs"] = [{"name": "hyb", "source": "test",
                      "file": "bench/configs/hyb.json", "reduced": [],
                      "why": "test"}]
    bm["workloads"] = [{"name": "hyb.column", "config": "hyb",
                        "traffic": "hyb", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(r)


def test_whole_cpu_run_of_a_hybrid_pair(hybrid_root):
    """The harness serves the cell's mix (6 classes, the proxy at 0.1,
    the rest to the hybrid oracle on the whole document) through the
    paged plane: every document resolved at its stage, the answers
    within the check's limit of the float32 reference (bf16 program:
    this size reads well under 0.05), the state traffic counted."""
    out = harness.run("hyb.column", 2**31 + 11, 1.0, True, root=hybrid_root,
                      require_tpu=False, cache=False, log=lambda *a: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 10
    m = out["metrics"]
    assert m["state_bytes_per_doc.column"]["value"] > 0
    assert m["docs_per_launch.column"]["value"] >= 1
