"""Trace reduction (bench/trace_reduce.py): on a hand-built trace whose
answer is known, and on a small trace recorded on a TPU v5e (two jitted
programs run three times between host spans)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import trace_reduce as TR  # noqa: E402

RECORDED = os.path.join(BENCH, "testdata", "v5e_small.xplane.pb")

# times in ns: window [0, 10000]; ops [1000, 3000] and [6000, 7000];
# a host step span over [2500, 5000]
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_paged_step(42)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 2500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } } }
'''


def test_synthetic_trace_busy_gaps_and_programs():
    from jax.profiler import ProfileData
    s = TR.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert s.n_chips == 1
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3e-6)          # overlap counted once
    assert s.gaps_by_host_s == pytest.approx({"none": 4e-6,
                                              "bench.step": 3e-6})
    assert s.modules_s == pytest.approx({"jit_paged_step": 6e-6})
    assert s.op_time("kernel") == pytest.approx(1e-6)
    assert s.module_time(r"jit_(paged|gather)_step") == pytest.approx(6e-6)
    assert s.top_gaps(1) == [["none", pytest.approx(4e-6)]]


def test_interval_helpers():
    assert TR.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert TR.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]
    assert TR.gaps([(2, 5), (8, 10)], 0, 12) == [(0, 2), (5, 8), (10, 12)]


def test_recorded_v5e_trace():
    s = TR.reduce_file(RECORDED)
    assert s.n_chips == 1
    assert 0 < s.busy_s < s.window_s
    assert sum(s.gaps_by_host_s.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.modules_s and s.ops_s
    assert "bench.wait" in s.gaps_by_host_s


def test_synthetic_trace_keeps_whole_programs_and_kernels():
    from jax.profiler import ProfileData
    text = SYNTHETIC.replace('name: "kernel"', 'name: "tpu_custom_call.7"')
    s = TR.reduce_profile(ProfileData.from_text_proto(text))
    assert s.module_events == [("jit_paged_step",
                                pytest.approx(1e-6), pytest.approx(7e-6))]
    assert s.kernel_events == [(pytest.approx(6e-6), pytest.approx(7e-6))]


def test_traced_launches_follow_the_order_of_enqueue():
    """Two launches in flight: a program is matched to the last launch
    enqueued before it began, the next program to the next launch, and a
    launch enqueued after its program began breaks the chain."""
    import harness
    t0 = 100.0
    logs = [harness.Launch(model="m", t_enqueue=t0 + t,
                           required={}, computed_tokens=0)
            for t in (-0.30, 0.01, 0.30, 0.55, 0.95)]
    trace = TR.TraceSummary(
        window_s=1.2, busy_s=1.0, n_chips=1,
        module_events=[("jit_paged_step", 0.25, 0.50),
                       ("jit_paged_step", 0.50, 0.75),
                       ("jit_other", 0.76, 0.77),
                       ("jit_paged_step", 0.80, 0.90),
                       ("jit_paged_step", 1.00, 1.10)],
        kernel_events=[(0.30, 0.31), (0.55, 0.60), (0.70, 0.72)])
    run = harness.Run(cell=None, seconds=1.0, t_open=t0, t_close=t0 + 1.2,
                      setup_s=0.0, served=[], launches=[], launch_log=logs,
                      trace=trace, trace_span=(t0, t0 + 1.2))
    got = [(logs.index(l), round(d, 6), round(k, 6))
           for l, d, k in run.traced_launches()]
    assert got == [(1, 0.25, 0.01), (2, 0.25, 0.07), (3, 0.10, 0.0),
                   (4, 0.10, 0.0)]
