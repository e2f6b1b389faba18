"""Architecture modules (bench/arch/<model_type>.py), found by each model
entry's ``model_type``.

The Qwen3 module gives what the benchmark gave before its code moved
there: the same weights bit for bit, the same reference logits and the
same work counts (the constants below were read from the benchmark as it
was before the move, under jax and jaxlib 0.9.0 on an x86-64 CPU).  A stand-in architecture written to a
fresh ``arch`` directory is found and used by a whole run, and a
``model_type`` that names no module stops the run with the path looked
for.
"""
import hashlib
import json
import os
import shutil
import sys
import textwrap
from types import SimpleNamespace

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import harness  # noqa: E402

from repro.serving.telemetry import LaunchRecord  # noqa: E402

QWEN3 = harness.load_arch(ROOT, "qwen3")
SMALL = {"name": "t", "model_type": "qwen3", "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512, "max_position_embeddings": 4096,
         "rope_theta": 10000, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": True, "hidden_act": "silu"}

# sha256 of each leaf's bytes (first 16 hex digits), in tree order
_NORMS = {"['final_norm']['scale']": "2f20cd03c9cd392a",
          "['stages'][0]['attn']['k_norm']['scale']": "b638277a8690e175",
          "['stages'][0]['attn']['q_norm']['scale']": "b638277a8690e175",
          "['stages'][0]['norm1']['scale']": "02722f124d0f1736",
          "['stages'][0]['norm2']['scale']": "02722f124d0f1736"}
PARENT_LEAVES = {
    (7, 0): {"['embed']['table']": "ecba575494b1d07b",
             "['stages'][0]['attn']['wk']": "229a8ef7e7ac44f2",
             "['stages'][0]['attn']['wo']": "307f48063e9b335e",
             "['stages'][0]['attn']['wq']": "02fb25f93cd9a9be",
             "['stages'][0]['attn']['wv']": "97b006d526ede95d",
             "['stages'][0]['mlp']['w1']": "ced7f526a120b72f",
             "['stages'][0]['mlp']['w2']": "859cc430c8a66592",
             "['stages'][0]['mlp']['w3']": "d86603b762aefba9", **_NORMS},
    (2**33 + 5, 1): {"['embed']['table']": "94b23ebf6e56c16e",
                     "['stages'][0]['attn']['wk']": "c4e83d118997814b",
                     "['stages'][0]['attn']['wo']": "cb49391538cd9ead",
                     "['stages'][0]['attn']['wq']": "8f719db4bbc837b3",
                     "['stages'][0]['attn']['wv']": "f154be39feb2f0a6",
                     "['stages'][0]['mlp']['w1']": "b13ddd24a922b82d",
                     "['stages'][0]['mlp']['w2']": "faeb4ca0a0df35ef",
                     "['stages'][0]['mlp']['w3']": "66b04372f57bdb2b",
                     **_NORMS},
}

# class logits of SMALL (seed 2**31 + 3, role 1) after PROMPT, 4 classes;
# compared to 1e-6, since another CPU or XLA may round the last bits
# otherwise (the control moves them by 1e-3 and more)
PROMPT_TEXT = "the quick brown fox jumps over the lazy dog " * 3
PARENT_LOGITS = {
    False: [0.17047923803329468, -0.005216978490352631,
            -0.004036150872707367, 0.560058057308197],
    True: [0.1660677194595337, -0.006272412836551666,
           -0.011821955442428589, 0.5478743314743042],
}

# stage_work(m, cached, doc_tokens, op_tokens, n_classes) on these cases:
# (tokens, linear_flops, attn_flops, attn_bytes, head_flops, flops)
WORK_CASES = [(0, 1500, 60, 2), (150, 1500, 60, 2), (0, 37, 60, 4),
              (205, 265, 0, 4), (1025, 2048, 60, 2)]
WORK_KEYS = ("tokens", "linear_flops", "attn_flops", "attn_bytes",
             "head_flops", "flops")
PARENT_WORK = {
    "Qwen3-0.6B": (28, [
        (1560, 1374053990400, 279283630080, 536739840, 4096, 1653337624576),
        (1410, 1241933414400, 276685946880, 502333440, 4096, 1518619365376),
        (97, 85437972480, 1090224128, 33374208, 8192, 86528204800),
        (60, 52848230400, 3241082880, 44154880, 8192, 56089321472),
        (1083, 953910558720, 389265063936, 490176512, 4096, 1343175626752)]),
    "Qwen3-1.7B": (28, [
        (1560, 4396972769280, 279283630080, 536739840, 8192, 4676256407552),
        (1410, 3974186926080, 276685946880, 502333440, 8192, 4250872881152),
        (97, 273401511936, 1090224128, 33374208, 16384, 274491752448),
        (60, 169114337280, 3241082880, 44154880, 16384, 172355436544),
        (1083, 3052513787904, 389265063936, 490176512, 8192,
         3441778860032)]),
    "Qwen3-4B": (36, [
        (1560, 11335945420800, 718157905920, 1150156800, 10240,
         12054103336960),
        (1410, 10245950668800, 711478149120, 1061683200, 10240,
         10957428828160),
        (97, 704863272960, 2803433472, 71516160, 20480, 707666726912),
        (60, 435997900800, 8334213120, 74465280, 20480, 444332134400),
        (1083, 7869762109440, 1000967307264, 949616640, 10240,
         8870729426944)]),
}


def _published_models():
    out = []
    for f in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        cfg = json.load(open(os.path.join(BENCH, "configs", f)))
        out += [(f, role, m) for role, m in cfg["models"].items()]
    return out


@pytest.mark.parametrize("seed,role", sorted(PARENT_LEAVES))
def test_make_params_matches_the_parent_bitwise(seed, role):
    p = QWEN3.make_params(SMALL, seed, role)
    got = {jax.tree_util.keystr(path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert got == PARENT_LEAVES[(seed, role)]


@pytest.mark.parametrize("control", [False, True])
def test_class_logits_match_the_parent(control):
    import reference as REF
    p = QWEN3.make_params(SMALL, 2**31 + 3, 1)
    tokens = REF.tokenize(PROMPT_TEXT, SMALL["vocab_size"]) + [9, 10, 11]
    z = QWEN3.class_logits(p, SMALL, tokens, 4, control=control)
    assert z.dtype == np.float64
    np.testing.assert_allclose(z, PARENT_LOGITS[control], rtol=0, atol=1e-6)


@pytest.mark.parametrize("f,role,m", _published_models(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_stage_work_matches_the_parent(f, role, m):
    assert m["model_type"] == "qwen3"
    layers, work = PARENT_WORK[m["name"]]
    assert QWEN3.paged_attention_layers(m) == layers
    got = [tuple(QWEN3.stage_work(m, *c)[k] for k in WORK_KEYS)
           for c in WORK_CASES]
    assert got == work


# ---------------------------------------------------- a stand-in architecture
STUB = textwrap.dedent('''\
    """A stand-in architecture: Qwen3's functions, each call recorded, its
    work counts tagged and one paged flash kernel a pass."""
    import importlib.util

    _spec = importlib.util.spec_from_file_location("stub_base", {base!r})
    BASE = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(BASE)
    CALLS = []


    def _recorded(name):
        def call(*a, **k):
            CALLS.append(name)
            return getattr(BASE, name)(*a, **k)
        return call


    model_config = _recorded("model_config")
    make_params = _recorded("make_params")
    class_logits = _recorded("class_logits")


    def stage_work(*a, **k):
        CALLS.append("stage_work")
        return dict(BASE.stage_work(*a, **k), stub_visits=1)


    def paged_attention_layers(m):
        CALLS.append("paged_attention_layers")
        return 1
''')


def _root(tmp_path, model_type):
    """A checkout holding one small cell whose models name
    ``model_type``, and, for "stub", the stand-in beside no other
    architecture."""
    r = tmp_path / "checkout"
    for d in ("configs", "traffic", "arch"):
        (r / "bench" / d).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "metrics"), r / "bench" / "metrics")
    (r / "bench" / "arch" / "stub.py").write_text(
        STUB.format(base=os.path.join(BENCH, "arch", "qwen3.py")))
    models = {role: dict(SMALL, name=role, num_hidden_layers=layers,
                         hidden_size=128, intermediate_size=256)
              for role, layers in (("proxy", 2), ("oracle", 3))}
    for m in models.values():
        m.pop("model_type")
        if model_type is not None:
            m["model_type"] = model_type
    cfg = {"name": "small", "reduced": [], "dtype": "bfloat16",
           "models": models,
           "serving": {"batch": 1, "inflight": 1, "attn_impl": "naive",
                       "block_q": 16, "block_kv": 16, "op_reserve": 8,
                       "arena_slots": {"proxy": 2, "oracle": 1}}}
    mix = {"loop": "closed", "in_flight": 2, "tenants": 1, "classes": 2,
           "length": {"median": 24, "sigma": 0.3, "min": 17, "max": 32},
           "operations": {"o_orig": 6}, "oracle_op": "o_orig",
           "stages": [["proxy", "o_orig", 0.5]], "exit_shares": [0.5, 0.5],
           "block": 4, "window_docs_per_s": 4.0, "sample": 2,
           "limits": {"class_logprob_gap": 0.02}}
    (r / "bench" / "configs" / "small.json").write_text(json.dumps(cfg))
    (r / "bench" / "traffic" / "small.json").write_text(json.dumps(mix))
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["configs"] = [{"name": "small", "source": "test",
                      "file": "bench/configs/small.json", "reduced": [],
                      "why": "test"}]
    bm["workloads"] = [{"name": "small.column", "config": "small",
                        "traffic": "small", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(r)


def test_stub_architecture_is_found_and_used(tmp_path, monkeypatch):
    root = _root(tmp_path, "stub")
    seen = {}
    real = harness.Run.__init__

    def keep(self, *a, **k):
        real(self, *a, **k)
        seen["run"] = self
    monkeypatch.setattr(harness.Run, "__init__", keep)
    out = harness.run("small.column", 2**31 + 21, 1.0, False, root=root,
                      require_tpu=False, cache=False, log=lambda *a: None)
    assert out["correct"], out["checks"]
    run = seen["run"]
    arch = run.cell.arch
    assert {mod.__file__ for mod in arch.values()} == \
        {os.path.join(root, "bench", "arch", "stub.py")}
    calls = set().union(*(mod.CALLS for mod in arch.values()))
    assert {"model_config", "make_params", "class_logits",
            "stage_work"} <= calls
    assert run.launch_log and all(l.required["stub_visits"] >= 1
                                  for l in run.launch_log)

    # the op-suffix reader takes the kernels in a pass from the module:
    # the stand-in's one, where Qwen3's 2-layer proxy would have two
    t0 = 100.0
    recs = [LaunchRecord(index=i, ts_start=0.0, model="proxy", cached_len=0,
                         f_len=64, ts_enqueue=t0 + d, ts_ready=t0 + r)
            for i, (d, r) in enumerate([(0.005, 0.021), (0.025, 0.061)])]
    trace = SimpleNamespace(
        module_events=[("jit_paged_step", 0.010, 0.020),
                       ("jit_paged_step", 0.030, 0.060)],
        kernel_events=[(0.011, 0.012), (0.013, 0.014), (0.032, 0.035),
                       (0.040, 0.045), (0.050, 0.052)])
    traced = harness.Run(cell=run.cell, seconds=1.0, t_open=t0,
                         t_close=t0 + 1, setup_s=0.0, served=[],
                         launches=recs, trace=trace,
                         trace_span=(t0, t0 + 0.07))
    share = harness.load_reader(root, "op_suffix_share.column")(traced)
    assert "paged_attention_layers" in set().union(
        *(mod.CALLS for mod in arch.values()))
    assert share == pytest.approx(100.0 * (0.060 - 0.040) / 0.030)


@pytest.mark.parametrize("model_type", [None, "nosuch"])
def test_model_type_without_a_module_stops_the_run(tmp_path, model_type):
    root = _root(tmp_path, model_type)
    want = os.path.join(root, "bench", "arch",
                        f"{model_type or '<model_type>'}.py")
    with pytest.raises(ValueError, match="looked for") as e:
        harness.run("small.column", 5, 1.0, False, root=root,
                    require_tpu=False, cache=False, log=lambda *a: None)
    assert want in str(e.value)
