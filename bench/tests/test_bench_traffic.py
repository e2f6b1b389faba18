"""The traffic generator (bench/traffic.py) and how the harness finds a
cell's files by name."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import traffic as TR  # noqa: E402

BIG_SEED = 2**31 + 987_654_321


def _mix(name):
    return TR.load_mix(os.path.join(BENCH, "traffic", f"{name}.json"))


@pytest.mark.parametrize("name", ["column", "short-open"])
def test_same_seed_same_documents_and_schedule(name):
    a, b = TR.Traffic(_mix(name), BIG_SEED), TR.Traffic(_mix(name), BIG_SEED)
    for k in (0, 1, 63, 64, 200):
        assert a.doc(k) == b.doc(k)
    assert a.operations() == b.operations()


@pytest.mark.parametrize("name", ["column", "short-open"])
def test_seeds_share_the_work_of_each_block(name):
    mix = _mix(name)
    a, b = TR.Traffic(mix, 1), TR.Traffic(mix, BIG_SEED)
    K = mix["block"]
    for blk in range(3):
        da = [a.doc(blk * K + i) for i in range(K)]
        db = [b.doc(blk * K + i) for i in range(K)]
        assert sorted(d.n_tokens for d in da) == sorted(d.n_tokens for d in db)
        assert sorted(d.exit_stage for d in da) == \
            sorted(d.exit_stage for d in db)
        assert [d.n_tokens for d in da] != [d.n_tokens for d in db]
        for d in da:
            assert len(d.text.split()) == d.n_tokens
            assert mix["length"]["min"] <= d.n_tokens <= mix["length"]["max"]


def test_exit_shares_are_exact_per_block():
    mix = _mix("column")
    t = TR.Traffic(mix, 7)
    exits = [t.doc(k).exit_stage for k in range(mix["block"])]
    counts = TR.stratified_counts(mix["exit_shares"], mix["block"])
    assert [exits.count(s) for s in range(len(counts))] == counts
    assert sum(counts) == mix["block"]


def test_open_loop_arrivals_rise_and_each_block_lasts_the_same():
    mix = _mix("short-open")
    K = mix["block"]
    for seed in (3, BIG_SEED):
        t = TR.Traffic(mix, seed)
        arr = [t.arrival(k) for k in range(3 * K)]
        assert all(x < y for x, y in zip(arr, arr[1:]))
        span = sum(TR.exponential_gaps(mix["rate_per_s"], K))
        assert arr[K - 1] == pytest.approx(span)
        assert arr[2 * K - 1] == pytest.approx(2 * span)


def test_column_window_is_whole_blocks_sized_to_the_seconds():
    mix = _mix("column")
    t = TR.Traffic(mix, BIG_SEED)
    K, rate = mix["block"], mix["window_docs_per_s"]
    for seconds in (1.0, 10.0, 51.0, 200.0):
        n = t.window_docs(seconds)
        assert n % K == 0 and n >= K
        assert abs(n - seconds * rate) <= K / 2 or n == K


def test_open_window_holds_every_document_scheduled_in_it():
    mix = _mix("short-open")
    t = TR.Traffic(mix, BIG_SEED)
    n = t.window_docs(51.0)
    assert t.arrival(n - 1) < mix["ramp_s"] + 51.0 <= t.arrival(n)


def test_stratified_lengths_follow_the_median():
    ls = TR.stratified_lengths({"median": 1500, "sigma": 0.6, "min": 1025,
                                "max": 2048}, 64)
    assert ls == sorted(ls) and 1025 <= ls[0] and ls[-1] <= 2048
    assert 1300 < ls[32] < 1700


def test_harness_finds_new_cell_config_and_metric_by_name(tmp_path):
    """A later change adds a cell by adding files: a configuration, a mix,
    a metric reader and entries in BENCHMARK.json, editing no harness file."""
    root = tmp_path
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    shutil.copytree(os.path.join(BENCH, "arch"), root / "bench" / "arch")
    shutil.copy(os.path.join(BENCH, "configs", "qwen3-0.6b_qwen3-1.7b.json"),
                root / "bench" / "configs" / "new-pair.json")
    shutil.copy(os.path.join(BENCH, "traffic", "column.json"),
                root / "bench" / "traffic" / "new-mix.json")
    (root / "bench" / "metrics" / "new_counter.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["configs"].append({"name": "new-pair", "source": "s",
                          "file": "bench/configs/new-pair.json",
                          "reduced": [], "why": "w"})
    bm["workloads"].append({"name": "new-pair.new-mix", "config": "new-pair",
                            "traffic": "new-mix", "chips": 1, "why": "w"})
    bm["per_layer"].append({"name": "new_counter.x", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler", "moves": "setup_s",
                            "workloads": ["new-pair.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell("new-pair.new-mix", str(root))
    assert cell.config["models"]["oracle"]["name"] == "Qwen3-1.7B"
    assert cell.arch["oracle"].__file__ == str(root / "bench" / "arch" /
                                               "qwen3.py")
    assert cell.mix["loop"] == "closed"
    assert [m["name"] for m in cell.e2e] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_counter.x"]
    assert harness.load_reader(str(root), "new_counter.x")(None) == 42.0


def test_reader_of_a_split_metric_falls_back_to_its_base_name(tmp_path):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "q.py").write_text(
        "def read(run):\n    return 1.0\n")
    (tmp_path / "bench" / "metrics" / "q.b.py").write_text(
        "def read(run):\n    return 2.0\n")
    assert harness.load_reader(str(tmp_path), "q.a")(None) == 1.0
    assert harness.load_reader(str(tmp_path), "q.b")(None) == 2.0
    with pytest.raises(FileNotFoundError):
        harness.load_reader(str(tmp_path), "r.a")


def test_every_metric_in_the_benchmark_has_a_reader():
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.load_reader(ROOT, m["name"])), m["name"]
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.e2e and cell.per_layer, w["name"]
