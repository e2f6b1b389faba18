"""The output check (bench/check.py) at a size a CPU test run holds.

Whole runs of the harness, with the look for a chip skipped, on a small
Qwen3-shaped pair: a sound run comes out correct; runs with the timed
path broken underneath come out not correct; and the control (the
reference with fp8 weights in the program's place) reads above the limit
the sound run stays under.
"""
import json
import math
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import check as CK  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402
import work as WK  # noqa: E402

CELL = "small.column"
LIMIT = 0.02          # at this size: sound runs read ~1e-3, faults > 0.05


def _model(name, layers):
    return {"name": name, "source": "test", "model_type": "qwen3",
            "hidden_size": 256,
            "intermediate_size": 512, "num_hidden_layers": layers,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "vocab_size": 1024,
            "max_position_embeddings": 4096, "rope_theta": 10000,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
            "hidden_act": "silu"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("benchroot")
    (r / "bench" / "configs").mkdir(parents=True)
    (r / "bench" / "traffic").mkdir()
    for d in ("arch", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), r / "bench" / d)
    cfg = {"name": "small", "reduced": [], "dtype": "bfloat16",
           "models": {"proxy": _model("p", 2), "oracle": _model("o", 3)},
           "serving": {"batch": 1, "inflight": 1, "attn_impl": "naive",
                       "block_q": 16, "block_kv": 16, "op_reserve": 8,
                       "arena_slots": {"proxy": 2, "oracle": 1}}}
    mix = {"loop": "closed", "in_flight": 2, "tenants": 1, "classes": 2,
           "length": {"median": 24, "sigma": 0.3, "min": 17, "max": 32},
           "operations": {"o_orig": 6}, "oracle_op": "o_orig",
           "stages": [["proxy", "o_orig", 0.5]], "exit_shares": [0.5, 0.5],
           "block": 8, "window_docs_per_s": 8.0, "sample": 3,
           "limits": {"class_logprob_gap": LIMIT}}
    (r / "bench" / "configs" / "small.json").write_text(json.dumps(cfg))
    (r / "bench" / "traffic" / "small.json").write_text(json.dumps(mix))
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["configs"] = [{"name": "small", "source": "test",
                      "file": "bench/configs/small.json", "reduced": [],
                      "why": "test"}]
    bm["workloads"] = [{"name": CELL, "config": "small", "traffic": "small",
                        "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(r)


def _run(root, seed=2**31 + 5):
    return harness.run(CELL, seed, 1.0, False, root=root, require_tpu=False,
                       cache=False, log=lambda *a: None)


def test_sound_run_is_correct(root):
    out = _run(root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["class_logprob_gap"]["value"] < LIMIT / 4
    assert list(out)[-1] == "checks"
    assert out["metrics"]["docs_per_s"]["value"] > 0


def test_traced_run_reads_the_launch_metrics(root):
    out = harness.run(CELL, 2**31 + 6, 1.0, True, root=root,
                      require_tpu=False, cache=False, log=lambda *a: None)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert 0 < m["padded_token_share.column"]["value"] < 100
    assert 0 < m["arena_live_share.column"]["value"] < 100
    assert m["docs_per_launch.column"]["value"] >= 1
    assert "docs_per_s" not in m and "breakdown" in out


def test_answer_altered_where_produced_is_not_correct(root, monkeypatch):
    from repro.serving.engine import LMBackend
    real = LMBackend.class_confidences

    def flipped(self, logits, n_classes):
        pred, conf = real(self, logits, n_classes)
        return (pred + 1) % n_classes, conf
    monkeypatch.setattr(LMBackend, "class_confidences", flipped)
    out = _run(root)
    assert not out["correct"]
    assert out["checks"]["class_logprob_gap"]["value"] > LIMIT


def test_step_that_leaves_the_cache_unchanged_is_not_correct(root,
                                                             monkeypatch):
    """The document prefix is computed but never written to the arena:
    the operation then reads a cache the document never reached."""
    from repro.models.model import LM
    real = LM.extend

    def dropped(self, params, batch, states, *a, **k):
        logits, _ = real(self, params, batch, states, *a, **k)
        return logits, states
    monkeypatch.setattr(LM, "extend", dropped)
    out = _run(root)
    assert not out["correct"]
    assert out["checks"]["class_logprob_gap"]["value"] > LIMIT


def test_control_reads_above_the_limit(root):
    """The control: fp8 weights, one scale per output channel, in the
    program's place, on the sample a run of the cell checks, judged by
    the check itself."""
    cell = harness.load_cell(CELL, root)
    for seed in (11, 2**31 + 12):
        out = control.control_run(cell, seed, 1.0)
        assert not out["correct"]
        assert out["checks"]["class_logprob_gap"]["value"] > LIMIT
        assert out["checks"]["exit_stage_mismatch"]["value"] == 0


def test_launch_log_counts_the_documents_required_work(root, monkeypatch):
    """The window's launches, as logged, require exactly the work of the
    documents the window resolved: padding and prefixes computed again
    after an eviction are not counted."""
    cell = harness.load_cell(CELL, root)
    seen = {}
    real = harness.Run.__init__

    def keep(self, *a, **k):
        real(self, *a, **k)
        seen["run"] = self
    monkeypatch.setattr(harness.Run, "__init__", keep)
    out = _run(root)
    run = seen["run"]
    assert out["correct"]
    mix = cell.mix
    stages = [tuple(s) for s in mix["stages"]] + [
        ("oracle", mix["oracle_op"], 1.0)]
    models = {role: (m, cell.arch[role])
              for role, m in cell.config["models"].items()}
    want = {}
    for s in run.in_window():
        for model, w in WK.document_work(
                models, stages, range(s.exit_stage + 1), s.doc.n_tokens,
                mix["operations"], 2).items():
            want[model] = want.get(model, 0) + w["flops"]
    got = {}
    for launch in run.launched_in_window():
        got[launch.model] = got.get(launch.model, 0) + \
            launch.required["flops"]
        assert launch.computed_tokens >= launch.required["tokens"]
    assert got == want
    assert len(run.in_window()) == mix["block"]


def test_answer_gap_reads_flips_and_confidence():
    ref = np.array([1.0, 0.0])
    lp = ref - math.log(math.exp(1.0) + 1.0)
    assert CK.answer_gap(ref, 0, math.exp(lp[0])) == pytest.approx(0, abs=1e-12)
    assert CK.answer_gap(ref, 1, math.exp(lp[1])) == pytest.approx(1.0)
    assert CK.answer_gap(ref, 0, 0.5) == pytest.approx(abs(math.log(0.5)
                                                           - lp[0]))
    assert CK.answer_gap(ref, 0, float("nan")) == math.inf
