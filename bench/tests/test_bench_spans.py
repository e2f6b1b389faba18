"""The serving plane's own spans and the readers built on them
(bench/serve_spans.py, ``op_suffix_share``, ``queue_wait_ms_p95``): a
real profiler trace of a small server run, and the program matcher and
op-suffix boundary on a hand-built trace."""
import math
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import serve_spans as SS  # noqa: E402
import trace_reduce as TRR  # noqa: E402

from repro.config import resolve  # noqa: E402
from repro.configs import get_reduced  # noqa: E402
from repro.core.tasks import Cascade, Task, TaskConfig  # noqa: E402
from repro.data.documents import generate_corpus  # noqa: E402
from repro.data.tokenizer import HashWordTokenizer  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.models.runtime import CPU_TEST  # noqa: E402
from repro.serving.engine import CascadeServer, LMBackend  # noqa: E402
from repro.serving.scheduler import RetryPolicy  # noqa: E402
from repro.serving.telemetry import LaunchRecord  # noqa: E402

SERVE_SPANS = {"serve.sched", "serve.make_room", "serve.assemble",
               "serve.dispatch", "serve.sync", "serve.readout",
               "serve.route", "serve.idle_wait"}
OPS = {"o_orig": "does this overturn a lower court decision",
       "sur_1": "is a lower court mentioned"}
NEVER = {0: math.inf, 1: math.inf}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                      num_layers=2)
    lm = LM(resolve(cfg, tp=1), CPU_TEST)
    return lm, lm.init(jax.random.PRNGKey(1))


def _server(tiny, **kw):
    lm, params = tiny
    tokz = HashWordTokenizer(vocab_size=512)
    backends = {name: LMBackend(name=name, model=lm, params=params,
                                tokenizer=tokz, s_alloc=512)
                for name in ("proxy", "oracle")}
    return CascadeServer(backends, OPS, n_classes=2, batch_size=2, **kw)


def _docs(n):
    return {d.doc_id: d.text for d in generate_corpus(n, avg_lines=6,
                                                      seed=7)}


def _host_spans(planes):
    """(name, start ns, end ns, stats) of every ``bench.*``/``serve.*``
    span on the trace's host planes."""
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
             dict(ev.stats))
            for p in planes if not p.name.startswith("/device:")
            for ev in TRR._events(p, None)
            if ev.name.startswith(("bench.", "serve."))]


# ------------------------------------------------ a real profiler trace
def test_serve_spans_in_a_recorded_trace(tiny):
    """A small server run under ``jax.profiler`` (Python tracer off):
    every ``serve.*`` span is on the host plane, inside the caller's
    ``bench.step`` spans, with its ``launch`` argument where it has one;
    ``trace_reduce``'s innermost-span rule puts an idle gap inside a
    ``serve.sync`` down to ``serve.sync``."""
    srv = _server(tiny, inflight=2,
                  retry=RetryPolicy(max_retries=2, backoff_base=0.02))
    h = srv.register(Cascade([Task(TaskConfig("proxy", "sur_1", 0.5),
                                   NEVER)]))
    oracle = srv.backends["oracle"]
    real, failed = oracle.dispatch_group, []

    def fail_once(*args, **kwargs):     # a retry makes drain idle-wait
        if not failed:
            failed.append(1)
            raise RuntimeError("injected dispatch failure")
        return real(*args, **kwargs)

    oracle.dispatch_group = fail_once
    docs = _docs(4)
    futures = [h.submit(d, docs[d]) for d in sorted(docs)]
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while srv.pending():
                with jax.profiler.TraceAnnotation("bench.step"):
                    srv.drain()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(TRR.find_xplane(out)).planes)
    shutil.rmtree(out, ignore_errors=True)
    assert all(f.status == "resolved" for f in futures)

    spans = _host_spans(planes)
    serve = [s for s in spans if s[0].startswith("serve.")]
    assert {s[0] for s in serve} == SERVE_SPANS
    steps = [s for s in spans if s[0] == "bench.step"]
    assert steps and all(any(b[1] <= s[1] and s[2] <= b[2] for b in steps)
                         for s in serve)
    recs = [r for r in srv.telemetry.launches.items() if r.ok]
    synced = {s[3]["launch"] for s in serve if s[0] == "serve.sync"}
    assert synced == {r.index for r in recs}
    for name, _, _, args in serve:
        if name == "serve.dispatch":
            assert {"launch", "model", "bucket", "width", "new"} <= \
                set(args)
            assert args["model"] in ("proxy", "oracle")
    # a device that idles inside the longest sync: the gap is the sync's
    sync = max((s for s in serve if s[0] == "serve.sync"),
               key=lambda s: s[2] - s[1])
    lo, hi = [(s[1], s[2]) for s in spans if s[0] == "bench.window"][0]
    a = sync[1] + (sync[2] - sync[1]) // 4
    b = sync[2] - (sync[2] - sync[1]) // 4
    (gs, ge), = TRR.gaps(TRR.union([(lo, a), (b, hi)]), lo, hi)
    assert (gs, ge) == (a, b)
    triples = [(s, e, name) for name, s, e, _ in spans]
    assert TRR._activity(triples, (gs + ge) // 2) == "serve.sync"


# ------------------------------------------- a hand-built device trace
MS = 10**9                              # ps
KERNEL = ('%paged_decode_attention.2 = f32[8] custom-call(), '
          'custom_call_target="tpu_custom_call"')


def _trace(programs, kernels, window=(10, 200)):
    """A text-proto trace: ``programs`` as (name, start, end) and Pallas
    ``kernels`` as (start, end) on one chip, in ms, and the host's
    ``bench.window`` span."""
    names, meta = {}, []

    def mid(name):
        if name not in names:
            i = names[name] = len(names) + 1
            quoted = name.replace('"', '\\"')
            meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{quoted}" }} }}')
        return names[name]

    def ev(m, s, e):
        return (f"events {{ metadata_id: {m} offset_ps: {s * MS} "
                f"duration_ps: {(e - s) * MS} }}")

    ops = [ev(mid("%fusion.3 = f32[] fusion()"), s, e)
           for _, s, e in programs]
    ops += [ev(mid(KERNEL), s, e) for s, e in kernels]
    mods = [ev(mid(f"{n}(7)"), s, e) for n, s, e in programs]
    dev_meta = " ".join(meta)
    names.clear()
    meta.clear()
    host = ev(mid("bench.window"), *window)
    return (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
            f'{" ".join(ops)} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 '
            f'{" ".join(mods)} }} {dev_meta} }} '
            f'planes {{ id: 2 name: "/host:CPU" '
            f'lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }} '
            f'{" ".join(meta)} }}')


T0 = 100.0                      # perf_counter at the window's first instant
LO = 10                         # the window's start on the trace, ms


def _rec(index, model, dispatch, sync_end, decode_only=False):
    """A launch record whose ``serve.dispatch`` began and ``serve.sync``
    ended at the given trace times (ms)."""
    return LaunchRecord(index=index, ts_start=0.0, model=model,
                        cached_len=64 if decode_only else 0, f_len=64,
                        ts_enqueue=T0 + (dispatch - LO) * 1e-3,
                        ts_ready=T0 + (sync_end - LO) * 1e-3)


def _run(text, recs):
    from jax.profiler import ProfileData
    qwen3 = harness.load_arch(os.path.dirname(BENCH), "qwen3")
    cell = SimpleNamespace(config={"models": {
        "proxy": {"num_hidden_layers": 2},
        "oracle": {"num_hidden_layers": 3}}},
        arch={"proxy": qwen3, "oracle": qwen3})
    return harness.Run(cell=cell, seconds=1.0, t_open=T0, t_close=T0 + 1,
                       setup_s=0.0, served=[], launches=recs,
                       trace=TRR.reduce_profile(
                           ProfileData.from_text_proto(text)),
                       trace_span=(T0, T0 + 0.19))


def _reader(metric):
    return harness.load_reader(os.path.dirname(BENCH), metric)


# a program from before the window (2-15 ms), the window's first program
# (15-40, its kernels partly lost) that the reader leaves out, a decode-
# only oracle program dispatched before that one began (two in flight),
# a gather program the reader ignores, a proxy program with an op suffix,
# and one whose operation went through the extend (no decode kernel)
PROGRAMS = [("jit_paged_step", 2, 15), ("jit_paged_step", 15, 40),
            ("jit_paged_step", 40, 70), ("jit_gather_step", 70, 71),
            ("jit_paged_step", 71, 100), ("jit_paged_step", 101, 130)]
KERNELS = [(30, 31), (45, 46), (50, 51), (72, 75), (76, 79), (80, 81),
           (82, 83), (105, 110), (115, 120)]


def _recs():
    """Launch 0's program ran before the window; 1's is the window's
    first; 2's dispatch raised (no record, no program); 3 (decode-only)
    was dispatched before 1's program began."""
    return [_rec(0, "proxy", 1, 16), _rec(1, "proxy", 12, 41),
            _rec(3, "oracle", 14, 71, decode_only=True),
            _rec(4, "proxy", 43, 101), _rec(5, "proxy", 99, 131)]


def test_span_matcher_and_op_suffix_on_a_hand_built_trace():
    """Every program in the window is matched; the share leaves out 1's
    (the window's first, its kernels partly lost); 4's op suffix starts
    at its third kernel (2 layers); 5 has two kernels and no suffix."""
    recs = _recs()
    run = _run(_trace(PROGRAMS, KERNELS), recs)
    assert [m[0] for m in run.trace.module_events] == \
        ["jit_paged_step"] * 2 + ["jit_gather_step"] + ["jit_paged_step"] * 2
    progs = [(s, e) for n, s, e in run.trace.module_events
             if n == "jit_paged_step"]
    anchors = [SS.LaunchAnchor(r.index, r.ts_enqueue - T0,
                               r.ts_ready - T0) for r in recs]
    assert [(a.index, p) for a, p in SS.match_programs(progs, anchors)] == \
        [(1, 0), (3, 1), (4, 2), (5, 3)]
    # 3 from its first kernel, 25 of 30 ms; 4 from its third, 20 of 29;
    # 5 none of 29
    assert _reader("op_suffix_share.column")(run) == \
        pytest.approx(100.0 * 45 / 88)


def test_match_needs_the_sync_bound_with_two_in_flight():
    """Both launches were dispatched before either program began: the
    dispatch bound fits either pairing, the sync bound only one."""
    progs = [(1.0, 2.0), (2.0, 3.0)]
    a = [SS.LaunchAnchor(5, 0.1, 2.01), SS.LaunchAnchor(6, 0.2, 3.01)]
    assert [(x.index, p) for x, p in SS.match_programs(progs, a)] == \
        [(5, 0), (6, 1)]
    # a program from before the trace and a launch whose program left it
    progs = [(0.5, 0.9)] + progs
    a = a + [SS.LaunchAnchor(7, 2.5, 4.0)]
    assert [(x.index, p) for x, p in SS.match_programs(progs, a)] == \
        [(5, 1), (6, 2)]


def test_op_suffix_reader_uses_the_launch_timeline():
    """The reader matches programs through the records' dispatch and sync
    stamps: shift the stamps past the programs and nothing matches, and
    the reading is None, not a share of what is left."""
    recs = _recs()
    text = _trace(PROGRAMS, KERNELS)
    assert _reader("op_suffix_share.open")(_run(text, recs)) == \
        pytest.approx(100.0 * 45 / 88)
    late = [_rec(r.index, r.model, 150, 190, r.decode_only) for r in recs]
    assert _reader("op_suffix_share.open")(_run(text, late)) is None
    read = _reader("queue_wait_ms_p95.open")
    run = _run(text, recs)
    assert read(run) is None                   # no waits recorded
    recs[0].queue_wait_s = (0.010, 0.030)
    assert read(run) == pytest.approx(1e3 * float(
        np.percentile([0.010, 0.030], 95)))


def test_op_suffix_reader_reads_none_when_the_rule_breaks():
    """A program no launch accounts for, or one past the window's first
    with fewer kernels than the position rule needs, makes the share
    None."""
    recs = _recs()
    read = _reader("op_suffix_share.column")
    text = _trace(PROGRAMS, KERNELS)
    assert read(_run(text, recs[:3] + recs[4:])) is None      # 4 unmatched
    assert read(_run(text, recs[2:])) is None                 # 1 unmatched
    # launch 5 on the 3-layer oracle: 2 kernels, fewer than its extend's
    five = _rec(5, "oracle", 99, 131)
    assert read(_run(text, recs[:4] + [five])) is None
    # a decode-only program that lost every kernel
    lost = [k for k in KERNELS if not 40 <= k[0] < 70]
    assert read(_run(_trace(PROGRAMS, lost), recs)) is None
    assert read(_run(text, recs)) == pytest.approx(100.0 * 45 / 88)

