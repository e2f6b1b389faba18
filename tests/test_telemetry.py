"""Serving-plane telemetry: ring-buffer bounds, histogram quantiles,
metric-registry caps, ServeStats merge coverage, counters-level bitwise
inertness, span well-formedness under chaos, launch-segment accounting,
idle-wait measurement, and the Perfetto/Prometheus exporters."""
import dataclasses
import json
import math

import jax
import numpy as np
import pytest

from repro.config import resolve
from repro.configs import get_reduced
from repro.core.tasks import Cascade, Task, TaskConfig
from repro.data.documents import generate_corpus
from repro.data.tokenizer import HashWordTokenizer
from repro.models.model import LM
from repro.models.runtime import CPU_TEST
from repro.serving.engine import CascadeEngine, CascadeServer, LMBackend
from repro.serving.faults import FaultInjector, FaultPlan
from repro.serving.scheduler import (MERGE_STRATEGIES, TERMINAL_STATES,
                                     RetryPolicy, ServeStats)
from repro.serving.telemetry import (EV_FAULT, EV_LAUNCH, EV_SUBMIT,
                                     TERMINAL_EVENTS, Histogram,
                                     LaunchRecord, MetricRegistry, Telemetry,
                                     TraceBuffer, chrome_trace,
                                     write_chrome_trace)

OPS = {"o_orig": "does this overturn a lower court decision",
       "sur_1": "is a lower court mentioned"}
THR = {0: 0.7, 1: 0.7}
CASCADE = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), THR),
    Task(TaskConfig("proxy", "o_orig", 1.0), THR),
])


def _mk_model(seed):
    cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                      num_layers=2)
    m = LM(resolve(cfg, tp=1), CPU_TEST)
    return m, m.init(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def models():
    return {"proxy": _mk_model(1), "oracle": _mk_model(2)}


@pytest.fixture(scope="module")
def docs():
    return {d.doc_id: d.text
            for d in generate_corpus(8, avg_lines=10, seed=7)}


def mk_backends(models, tokz=None):
    tokz = tokz or HashWordTokenizer(vocab_size=512)
    return {name: LMBackend(
        name=name, model=m, params=p, tokenizer=tokz,
        rate_per_token=1.0 if name == "oracle" else 0.06, s_alloc=512)
        for name, (m, p) in models.items()}


def mk_server(models, **kw):
    kw.setdefault("retry", RetryPolicy(max_retries=2, backoff_base=0.0))
    return CascadeServer(mk_backends(models), OPS, n_classes=2,
                         batch_size=4, **kw)


# ------------------------------------------------------------ trace buffer

def test_trace_buffer_drops_oldest_and_counts():
    buf = TraceBuffer(4)
    for i in range(4):
        buf.append(i)
    assert len(buf) == 4 and buf.dropped == 0 and buf.total == 4
    assert buf.items() == [0, 1, 2, 3]
    buf.append(4)                       # overwrites 0
    buf.append(5)                       # overwrites 1
    assert len(buf) == 4
    assert buf.dropped == 2
    assert buf.total == 6
    assert buf.items() == [2, 3, 4, 5]  # oldest-first surviving tail
    buf.clear()
    assert len(buf) == 0 and buf.dropped == 0 and buf.items() == []


def test_trace_buffer_rejects_zero_capacity():
    with pytest.raises(AssertionError):
        TraceBuffer(0)


# -------------------------------------------------------------- histogram

def test_histogram_quantiles_without_samples():
    h = Histogram()
    for v in (1e-4,) * 50 + (1e-2,) * 49 + (0.5,):
        h.observe(v)
    assert h.count == 100
    assert h.sum == pytest.approx(50 * 1e-4 + 49 * 1e-2 + 0.5)
    # bucket resolution is a factor of 2: quantiles land within the
    # observed value's bucket
    assert h.p50() <= 2e-4 * 2
    assert 1e-2 / 2 <= h.p99() <= 1e-2 * 2
    assert h.quantile(1.0) <= h.max_seen
    assert Histogram().p50() == 0.0


def test_histogram_overflow_bucket_uses_max_seen():
    h = Histogram(bounds=(1.0, math.inf))
    h.observe(100.0)
    assert h.quantile(0.99) <= 100.0
    assert h.max_seen == 100.0


# --------------------------------------------------------- metric registry

def test_registry_labels_and_snapshot():
    reg = MetricRegistry()
    reg.counter("hits", backend="proxy").inc()
    reg.counter("hits", backend="proxy").inc(2.0)
    reg.counter("hits", backend="oracle").inc()
    reg.gauge("depth").set(7)
    snap = reg.snapshot()
    assert snap["hits{backend=proxy}"] == 3.0
    assert snap["hits{backend=oracle}"] == 1.0
    assert snap["depth"] == 7.0
    assert reg.series_count() == 3


def test_registry_series_cap_overflows_to_sink():
    reg = MetricRegistry(max_series=2)
    reg.counter("c", k="a").inc()
    reg.counter("c", k="b").inc()
    sink = reg.counter("c", k="overflow_1")
    reg.counter("c", k="overflow_2").inc()
    assert reg.series_count() == 2
    assert reg.dropped_series == 2
    assert sink is reg._overflow["counter"]


def test_registry_kind_collision_asserts():
    reg = MetricRegistry()
    reg.counter("m")
    with pytest.raises(AssertionError):
        reg.gauge("m")


def test_prometheus_exposition_format():
    reg = MetricRegistry()
    reg.counter("serve_launches_total", backend="proxy").inc(3)
    reg.histogram("serve_wall_seconds").observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE serve_launches_total counter" in text
    assert 'serve_launches_total{backend="proxy"} 3.0' in text
    assert 'le="+Inf"' in text
    assert "serve_wall_seconds_count 1" in text
    # bucket counts are cumulative: the +Inf bucket equals the count
    inf_line = [ln for ln in text.splitlines() if 'le="+Inf"' in ln][-1]
    assert inf_line.endswith(" 1")


# ----------------------------------------------- ServeStats merge coverage

def test_merge_covers_every_numeric_field():
    """Satellite 1: ``merge_from`` walks ``dataclasses.fields``, so EVERY
    field must carry (or default to) a known strategy, and each strategy
    must actually propagate — a new counter can never silently drop."""
    src = ServeStats()
    src.record(0, 2, 10, 20, 0.5)
    src.record(1, 1, 5, 5, 0.25)
    src.latencies.extend([0.1, 0.2])
    for f in dataclasses.fields(ServeStats):
        kind = f.metadata.get("merge", "sum")
        assert kind in MERGE_STRATEGIES, f.name
        if kind in ("sum", "max") and not getattr(src, f.name):
            setattr(src, f.name, 3)
    src.batches = 99                    # shared: must NOT merge

    dst = ServeStats()
    dst.merge_from(src)
    for f in dataclasses.fields(ServeStats):
        kind = f.metadata.get("merge", "sum")
        got = getattr(dst, f.name)
        if kind == "shared":
            assert got == 0, f"{f.name} (shared) leaked through merge"
        elif kind == "stage":
            assert got == getattr(src, f.name), f.name
        else:
            assert got == getattr(src, f.name), f.name

    dst.merge_from(src)                 # second fold: sums double, max holds
    assert dst.evictions == 2 * src.evictions
    assert dst.retries == 2 * src.retries
    assert dst.arena_bytes_peak == src.arena_bytes_peak
    assert dst.stage_docs == [2 * v for v in src.stage_docs]
    assert dst.latencies == src.latencies * 2
    assert dst.batches == 0


def test_unannotated_field_defaults_to_sum():
    """A field added without ``_stat`` metadata merges as 'sum' instead of
    being skipped."""
    plain = dataclasses.make_dataclass(
        "PlainStats", [("new_counter", int, 0)], bases=(ServeStats,))
    a, b = plain(), plain()
    b.new_counter = 5
    a.merge_from(b)
    assert a.new_counter == 5


# ------------------------------------------- bitwise inertness of counters

def test_counters_level_is_bitwise_inert(models, docs):
    """Default-on ``level="counters"`` must not change preds, confs,
    per-document $, or the arena device state vs ``level="off"``."""
    outs, leaves = {}, {}
    for level in ("off", "counters"):
        eng = CascadeEngine(mk_backends(models), OPS, n_classes=2,
                            batch_size=4)
        eng.telemetry.level = level
        outs[level] = eng.run(CASCADE, docs)
        leaves[level] = [
            np.asarray(leaf)
            for name in sorted(eng.backends)
            for bucket in sorted(eng.backends[name]._arenas)
            for leaf in jax.tree_util.tree_leaves(
                eng.backends[name]._arenas[bucket].states)]
    a, b = outs["off"], outs["counters"]
    assert a.pred == b.pred
    assert a.conf == b.conf
    assert a.doc_cost == b.doc_cost
    assert len(leaves["off"]) == len(leaves["counters"])
    for la, lb in zip(leaves["off"], leaves["counters"]):
        assert np.array_equal(la, lb)


def test_level_off_records_nothing(models, docs):
    eng = CascadeEngine(mk_backends(models), OPS, n_classes=2, batch_size=4)
    eng.telemetry.level = "off"
    eng.run(CASCADE, docs)
    snap = eng.telemetry.snapshot()
    assert snap["counters"]["launch_records"] == 0
    assert snap["counters"]["metric_series"] == 0
    assert snap["counters"]["events_total"] == 0


# ------------------------------------------------- spans + launch timeline

def _chaos_drain(models, level="trace"):
    srv = mk_server(models)
    srv.telemetry.level = level
    inj = FaultInjector(FaultPlan(seed=23, launch_failure_p=0.3, nan_p=0.2,
                                  latency_spike_p=0.1, spike_s=1e-4,
                                  arena_loss_at=3)).install(srv)
    docs = {d.doc_id: d.text
            for d in generate_corpus(8, avg_lines=10, seed=7)}
    handles = [srv.register(CASCADE), srv.register(CASCADE)]
    futs = {}
    for k, h in enumerate(handles):
        for j, d in enumerate(sorted(docs)[k::2]):
            futs[(h.query_id, d)] = h.submit(d, docs[d], arrival=float(j))
    srv.drain()
    return srv, inj, futs


def test_spans_well_formed_under_chaos(models):
    srv, inj, futs = _chaos_drain(models)
    assert all(f.done and f.status in TERMINAL_STATES
               for f in futs.values())
    report = srv.telemetry.validate_spans(require_terminal=True)
    assert report["ok"], report["violations"]
    assert report["checked"] == len(futs)
    spans = srv.telemetry.spans()
    assert len(spans) == len(futs)
    kinds = {e[2] for evs in spans.values() for e in evs}
    assert EV_SUBMIT in kinds and EV_LAUNCH in kinds
    if sum(inj.counts.values()) - inj.counts["arena_losses"] > 0:
        assert EV_FAULT in kinds       # injections land in doc spans
    # terminal event kinds match the scheduler's terminal statuses
    for (qid, d), f in futs.items():
        rid = srv._ids[(qid, d)]
        assert spans[rid][-1][2] == f.status
        assert spans[rid][-1][2] in TERMINAL_EVENTS


def test_launch_segments_sum_to_wall(models):
    srv, _, _ = _chaos_drain(models, level="counters")
    tm = srv.telemetry
    recs = [r for r in tm.launches.items() if r.ok]
    assert recs, "chaos drain recorded no launches"
    for r in recs:
        total = r.sched_s + r.host_s + r.dispatch_s + r.sync_s
        assert total == pytest.approx(r.wall_s, rel=0.05), r
        assert r.width >= r.batch > 0
        assert 0.0 < r.occupancy <= 1.0
    assert tm.segments_sum_ok()
    snap = srv.telemetry_snapshot()
    assert snap["counters"]["segments_sum_ok"] is True
    assert snap["counters"]["launch_records"] == tm.launch_total
    assert snap["server"]["launches"] == srv._launches
    tl = snap["timeline"]
    assert tl["wall_s"] == pytest.approx(
        tl["sched_s"] + tl["host_s"] + tl["dispatch_s"] + tl["sync_s"],
        rel=0.05)
    assert tl["host_overhead_s"] == tl["host_s"] + tl["dispatch_s"]


def test_trace_ring_overflow_skips_truncated_spans(models):
    srv = mk_server(models)
    srv.telemetry.level = "trace"
    srv.telemetry.events = TraceBuffer(8)        # tiny ring: force drops
    docs = {d.doc_id: d.text
            for d in generate_corpus(6, avg_lines=10, seed=7)}
    h = srv.register(CASCADE)
    for j, d in enumerate(sorted(docs)):
        h.submit(d, docs[d], arrival=float(j))
    srv.drain()
    tm = srv.telemetry
    assert tm.events.dropped > 0
    assert len(tm.events) == 8
    assert tm.events.total == tm.events.dropped + len(tm.events)
    report = tm.validate_spans(require_terminal=True)
    assert report["ok"], report["violations"]    # truncated spans skipped
    assert report["checked"] < len(docs)


def test_counters_level_skips_span_events(models, docs):
    srv = mk_server(models)                      # default level="counters"
    assert srv.telemetry.enabled and not srv.telemetry.tracing
    h = srv.register(CASCADE)
    for j, d in enumerate(sorted(docs)[:4]):
        h.submit(d, docs[d], arrival=float(j))
    srv.drain()
    tm = srv.telemetry
    assert tm.events.total == 0                  # no span events
    assert tm.launch_total > 0                   # timeline still recorded
    snap = tm.registry.snapshot()
    assert any(k.startswith("serve_launches_total") for k in snap)
    assert any(k.startswith("serve_docs_terminal_total") for k in snap)


def test_reset_clears_telemetry(models, docs):
    srv = mk_server(models)
    h = srv.register(CASCADE)
    h.submit(0, docs[0])
    srv.drain()
    assert srv.telemetry.launch_total > 0
    srv.reset()
    assert srv.telemetry.launch_total == 0
    assert srv.telemetry.registry.series_count() == 0


# ------------------------------------------------------------- idle wait

def test_idle_wait_sleeps_eligible_interval_and_is_measured(models, docs):
    srv = mk_server(models, retry=RetryPolicy(max_retries=3,
                                              backoff_base=0.02))
    # seed 8 fails the very first launch: the retried doc backs off and
    # drain must sleep the eligible interval out (measured)
    inj = FaultInjector(FaultPlan(seed=8, launch_failure_p=0.5))
    inj.install(srv)
    h = srv.register(CASCADE)
    for j, d in enumerate(sorted(docs)[:4]):
        h.submit(d, docs[d], arrival=float(j))
    srv.drain()
    assert inj.counts["launch_failures"] > 0
    tm = srv.telemetry
    assert tm.idle_wait_s > 0.0                  # drain slept, measured
    assert tm.idle_wait_s == pytest.approx(
        tm.snapshot()["timeline"]["idle_wait_s"])
    assert tm.registry.snapshot()[
        "serve_idle_wait_seconds_total"] == pytest.approx(tm.idle_wait_s)


def test_idle_wait_cap_bounds_single_sleep(models):
    srv = mk_server(models, idle_wait_cap=0.01,
                    retry=RetryPolicy(max_retries=1, backoff_base=10.0))
    # no eligible work, one request backing off far in the future
    h = srv.register(CASCADE)
    f = h.submit(0, "some words here", arrival=0.0)
    req = srv._requests[srv._ids[(h.query_id, 0)]]
    req.not_before = __import__("time").perf_counter() + 30.0
    import time
    t0 = time.perf_counter()
    srv._idle_wait()
    assert time.perf_counter() - t0 < 1.0        # capped, not 30 s
    assert 0.0 < srv.telemetry.idle_wait_s < 1.0
    req.not_before = 0.0
    srv.drain()
    assert f.done


# ------------------------------------------------------------ queue wait

def test_queue_wait_from_ready_to_dispatch(models, docs):
    """``LaunchRecord.queue_wait_s`` runs from the instant a document
    became ready to its launch's ``serve.dispatch`` entry: the first hop
    from its push at submit (a client's lag behind ``arrival_ts`` is not
    queue wait), an escalation hop from the push after the previous
    stage, a retry hop from the end of its backoff (the backoff is not
    waiting)."""
    import time
    inf = math.inf
    never = {0: inf, 1: inf}
    backoff = 0.05
    srv = mk_server(models, retry=RetryPolicy(max_retries=2,
                                              backoff_base=backoff))
    h = srv.register(Cascade([
        Task(TaskConfig("proxy", "sur_1", 0.25), never),
        Task(TaskConfig("proxy", "o_orig", 1.0), never)]))
    oracle = srv.backends["oracle"]
    real, failed_at = oracle.dispatch_group, []

    def fail_once(*args, **kwargs):
        if not failed_at:
            failed_at.append(time.perf_counter())
            raise RuntimeError("injected dispatch failure")
        return real(*args, **kwargs)

    oracle.dispatch_group = fail_once
    d = sorted(docs)[0]
    lag = 0.2
    arrival = time.perf_counter() - lag
    t_submit = time.perf_counter()
    h.submit(d, docs[d], arrival_ts=arrival)
    t_pushed = time.perf_counter()
    srv.drain()
    req = srv._requests[srv._ids[(h.query_id, d)]]
    assert req.status == "resolved" and req.exit_stage == 2
    first, second, failed, retried = srv.telemetry.launches.items()
    assert [r.model for r in (first, second, failed, retried)] == \
        ["proxy", "proxy", "oracle", "oracle"]
    assert (first.ok, second.ok, failed.ok, retried.ok) == \
        (True, True, False, True)
    for r in (first, second, retried):
        assert r.batch == 1 and len(r.queue_wait_s) == 1
    # first hop: from the push inside submit, the client's lag left out
    assert first.ts_enqueue - t_pushed <= first.queue_wait_s[0] <= \
        first.ts_enqueue - t_submit
    assert first.queue_wait_s[0] <= first.ts_enqueue - arrival - lag
    # escalation hop: from the push that followed the previous stage
    assert 0.0 <= second.queue_wait_s[0] <= \
        second.ts_enqueue - first.ts_ready
    # a dispatch that failed before enqueueing records no wait
    assert failed.queue_wait_s == ()
    # retry hop: from the end of the backoff, which is not waiting
    assert req.not_before >= failed_at[0] + backoff
    assert retried.queue_wait_s[0] == pytest.approx(
        retried.ts_enqueue - req.not_before, abs=1e-9)
    hist = srv.telemetry.registry.snapshot()
    assert hist["serve_queue_wait_seconds{backend=proxy}"]["count"] == 2
    assert hist["serve_queue_wait_seconds{backend=oracle}"]["count"] == 1


# -------------------------------------------------------------- exporters

def test_chrome_trace_layout(models, tmp_path):
    srv, _, futs = _chaos_drain(models)
    path = tmp_path / "trace.json"
    write_chrome_trace(srv.telemetry, str(path))
    trace = json.loads(path.read_text())
    evs = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"backend:proxy"} <= procs
    assert any(p.startswith("query:") for p in procs)
    slices = [e for e in evs if e["ph"] == "X"]
    launches = [e for e in slices if e.get("cat") == "launch"]
    spans = [e for e in slices if e.get("cat") == "span"]
    segs = [e for e in slices if e.get("cat") == "segment"]
    assert launches and spans and segs
    assert len(spans) == len(futs)               # one slice per document
    for e in launches:
        assert {"launch", "batch", "width", "occupancy",
                "copy_bytes"} <= set(e["args"])
        assert e["ts"] >= 0 and e["dur"] >= 0
    # per-launch segment slices tile the launch slice
    seg_names = {e["name"] for e in segs}
    assert seg_names == {"sched", "host", "dispatch", "sync"}
    insts = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "submit" for e in insts)
    assert any(e["name"] in TERMINAL_EVENTS for e in insts)


def test_chrome_trace_empty_telemetry():
    trace = chrome_trace(Telemetry(level="trace"))
    assert trace["traceEvents"] == []


def test_launch_record_derived_properties():
    r = LaunchRecord(index=0, ts_start=0.0, batch=3, width=4,
                     cached_len=64, f_len=64)
    assert r.occupancy == 0.75
    assert r.decode_only
    r2 = LaunchRecord(index=1, ts_start=0.0, cached_len=0, f_len=64)
    assert not r2.decode_only and r2.occupancy == 0.0


def test_decode_launch_roofline_helpers():
    from repro.launch.roofline import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert v5e.hbm_bw == 819e9 and v5e.flops_bf16 == 197e12
    assert "TPU v5e" in v5e.source
    # a device the table does not know has no roof
    assert device_peaks("cpu") is None
