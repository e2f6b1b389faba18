"""One run of one cell: build the served pair, warm every program the
cell's traffic uses, measure for ``seconds``, check what the window
produced against the float32 reference, and return the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json   the two models' published sizes and the serving
                          settings (launch width, launches in flight,
                          arena slots)
  arch/<model_type>.py    one architecture, named by each model entry's
                          published ``model_type``: ``model_config``,
                          ``make_params``, ``class_logits``,
                          ``stage_work``, ``paged_attention_layers``
  traffic/<traffic>.json  the mix, read by ``traffic.Traffic``
  metrics/<metric>.py     ``read(run) -> float | None`` for each metric
"""
from __future__ import annotations

import bisect
import gc
import importlib.util
import inspect
import json
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax

import check as CK
import peaks as PK
import trace_reduce as TRR
import traffic as TR
import weights as W
import work as WK
from repro.config import resolve
from repro.core.tasks import Cascade, Task, TaskConfig
from repro.data.tokenizer import HashWordTokenizer
from repro.models.model import LM
from repro.models.runtime import Runtime
from repro.serving.engine import CascadeServer, LMBackend
from repro.serving.scheduler import bucket_len

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_SUBDIR = os.path.join(".bench_cache", "jax")   # fixed: part of the key

GRACE_S = 60.0            # how long answers due in the window are waited for
RETIRE_NEVER = 10**9
STEP_PROGRAMS = r"jit_(paged|gather|prefix)_step"   # one per stage launch


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------- the spec
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    arch: Dict[str, Any]               # role -> arch/<model_type>.py
    traffic_name: str
    mix: Dict[str, Any]
    e2e: List[Dict[str, Any]]          # this cell's end-to-end metrics
    per_layer: List[Dict[str, Any]]    # this cell's per-layer metrics


def _applies(metric: Mapping[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    config = load_json(os.path.join(root, cfg["file"]))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config,
                arch={role: load_arch(root, m.get("model_type"))
                      for role, m in config["models"].items()},
                traffic_name=w["traffic"],
                mix=TR.load_mix(os.path.join(root, "bench", "traffic",
                                             f"{w['traffic']}.json")),
                e2e=e2e, per_layer=per_layer)


def load_reader(root: str, metric: str) -> Callable:
    """``metrics/<metric>.py``, or else the reader named without the
    metric's last ``.<suffix>``: ``mfu.py`` reads ``mfu.column`` and
    ``mfu.open``, a split of one quantity by the end-to-end metric it
    moves."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(root, "bench", "metrics",
                            f"{metric.rsplit('.', 1)[0]}.py")
    return _load_module(path, "bench_metric_" + metric).read


_ARCHS: Dict[str, Any] = {}     # path -> module, loaded once a process


def load_arch(root: str, model_type: Optional[str]):
    """``arch/<model_type>.py``: the architecture's builder, weights,
    reference and work counts (``arch/qwen3.py`` shows the five
    functions).  A model entry with no ``model_type``, or one that names
    no module, stops the run; there is no default architecture.  Each
    file is loaded once, so the models of one architecture share its
    module and the reference programs it has compiled."""
    path = os.path.join(root, "bench", "arch",
                        f"{model_type or '<model_type>'}.py")
    if not model_type or not os.path.isfile(path):
        raise ValueError(f"model_type {model_type!r} names no architecture "
                         f"module: looked for {path}")
    if path not in _ARCHS:
        _ARCHS[path] = _load_module(path, "bench_arch_" + model_type)
    return _ARCHS[path]


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- the device
def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s), JAX found "
                     f"{len(devs)} {info['platform']!r} device(s)")
    return info


def enable_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the program, which reads ``JAX_COMPILATION_CACHE_DIR``, is handed the
    same directory)."""
    path = os.path.join(root, CACHE_SUBDIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Programs lowered and compiled in this process (jax.monitoring)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1
            self.compile_s += duration


# -------------------------------------------------------- the served pair
def build_server(cell: Cell, seed: int, buckets: List[int],
                 operations: Dict[str, str]):
    """The pair behind one ``CascadeServer``, weights made on the device
    from the seed.  Arena capacity is fixed up front (``arena_slots`` a
    bucket, the byte budget at exactly those rows), because capacity is
    part of every compiled step's shape: the arena never grows, and a
    launch that needs room evicts instead."""
    cfg, sv = cell.config, cell.config["serving"]
    rt = Runtime(attn_impl=sv["attn_impl"], block_q=int(sv["block_q"]),
                 block_kv=int(sv["block_kv"]), remat=False)
    backends, params = {}, {}
    for role_i, role in enumerate(("proxy", "oracle")):
        m, arch = cfg["models"][role], cell.arch[role]
        lm = LM(resolve(arch.model_config(m, cfg["dtype"]), tp=1), rt)
        p = arch.make_params(m, seed, role_i)
        W.check_layout(p, jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
        slots = int(sv["arena_slots"][role])
        be = LMBackend(name=role, model=lm, params=p,
                       tokenizer=HashWordTokenizer(m["vocab_size"]),
                       op_reserve=int(sv["op_reserve"]), init_slots=slots,
                       retire_after=RETIRE_NEVER)
        be.byte_budget = sum((slots + 1) * be.slot_nbytes(b) for b in buckets)
        backends[role], params[role] = be, p
    jax.block_until_ready(params)
    server = CascadeServer(backends, operations,
                           n_classes=int(cell.mix["classes"]),
                           batch_size=int(sv["batch"]),
                           inflight=int(sv["inflight"]))
    return server, params


def routed_cascade(stages, exit_stage: int, n_classes: int):
    """A query whose documents resolve exactly at ``exit_stage``: the
    stages before it never accept, that stage always does (every
    confidence is at least 1/n_classes >= 0), the oracle takes the rest."""
    inf = float("inf")
    return Cascade([
        Task(TaskConfig(model, op, float(f)),
             {c: (0.0 if i >= exit_stage else inf) for c in range(n_classes)})
        for i, (model, op, f) in enumerate(stages)])


def widths(batch: int) -> List[int]:
    out, w = [], 1
    while w < batch:
        out.append(w)
        w *= 2
    return out + [batch]


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------- a run
@dataclass
class Served:
    doc: Any                       # traffic.Doc
    t_due: float                   # scheduled arrival (open) or submit
    t_submit: float
    t_done: Optional[float] = None
    status: Optional[str] = None
    pred: Optional[int] = None
    conf: Optional[float] = None
    exit_stage: Optional[int] = None
    error: Optional[str] = None


@dataclass
class Launch:
    """One stage launch as the harness saw it dispatched (host clock,
    ``perf_counter``), with the work it required (``work.py``: each
    document's true prefix at the stage's fraction and the operation,
    less what the model already computed for the document) and the
    tokens it computed (padded width x new and operation tokens)."""
    model: str
    t_enqueue: float                           # the jitted step was called
    required: Dict[str, int]
    computed_tokens: int
    arena: Optional[Tuple[int, float]] = None  # (reserved, live) bytes


class LaunchLog:
    """Wraps each backend's ``dispatch_group`` to log every launch.  With
    ``sample_arena`` it also reads the arenas right after each dispatch,
    while the launch's documents hold their rows."""

    def __init__(self, server, cell: "Cell", sample_arena: bool):
        self.launches: List[Launch] = []
        self._seen: Dict[Tuple[str, int], int] = {}
        self._server, self._sample = server, sample_arena
        self._models, self._arch = cell.config["models"], cell.arch
        self._classes = int(cell.mix["classes"])
        for be in server.backends.values():
            be.dispatch_group = self._wrap(be, be.dispatch_group)

    def _wrap(self, be, real):
        sig = inspect.signature(real)

        def dispatch_group(*args, **kwargs):
            ticket = real(*args, **kwargs)
            a = sig.bind(*args, **kwargs).arguments
            prefixes = [WK.true_prefix(len(a["doc_tokens"][d]),
                                       float(a["fraction"]))
                        for d in ticket.ids]
            self._log(be.name, ticket, prefixes,
                      int(a["f_len"]) - int(a["eff_c"]))
            return ticket
        return dispatch_group

    def _log(self, model: str, ticket, prefixes: List[int],
             n_new: int) -> None:
        m, req = self._models[model], {}
        for d, prefix, cached in zip(ticket.ids, prefixes, ticket.cached_d):
            done = self._seen.get((model, d), 0)
            w = self._arch[model].stage_work(
                m, min(max(int(cached), done), prefix), prefix,
                int(ticket.op_len), self._classes)
            self._seen[(model, d)] = max(done, prefix)
            for k, v in w.items():
                req[k] = req.get(k, 0) + v
        width = 1 << max(len(ticket.ids) - 1, 0).bit_length()
        self.launches.append(Launch(
            model=model, t_enqueue=float(ticket.ts_enqueue),
            required=req, computed_tokens=width * (n_new + ticket.op_len),
            arena=arena_sample(self._server) if self._sample else None))


@dataclass
class Run:
    """What a metric reader sees."""
    cell: Cell
    seconds: float
    t_open: float
    t_close: float
    setup_s: float
    served: List[Served]
    launches: List[Any]                        # LaunchRecords in the window
    launch_log: List[Launch] = field(default_factory=list)   # whole run
    trace: Any = None                          # trace_reduce.TraceSummary
    peaks: Optional[Dict[str, float]] = None
    trace_span: Tuple[float, float] = (0.0, 0.0)  # traced part of the window

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self) -> List[Served]:
        """Documents resolved in the window."""
        return [s for s in self.served if s.t_done is not None
                and self.t_open < s.t_done <= self.t_close
                and s.status == "resolved"]

    def launched_in_window(self) -> List[Launch]:
        return [l for l in self.launch_log
                if self.t_open <= l.t_enqueue < self.t_close]

    def traced_launches(self) -> List[Tuple[Launch, float, float]]:
        """(launch, device seconds of its step program, seconds of Pallas
        kernels inside that program) for every stage-step program that ran
        whole inside the trace.  The device runs launches in the order
        they are enqueued, so the first program is matched to the last
        launch enqueued before it began and each next one to the next
        launch; a launch enqueued after its program began breaks the chain
        and the match starts again from the clock."""
        if self.trace is None:
            return []
        t0 = self.trace_span[0]
        rx = re.compile(STEP_PROGRAMS)
        progs = [(t0 + s, t0 + e) for name, s, e in self.trace.module_events
                 if rx.search(name)]
        logs = sorted(self.launch_log, key=lambda l: l.t_enqueue)
        starts = [l.t_enqueue for l in logs]
        k_start = [s for s, _ in self.trace.kernel_events]
        out, j = [], None
        for s, e in progs:
            j = bisect.bisect_right(starts, s) - 1 if j is None else j + 1
            if j < 0 or j >= len(logs) or logs[j].t_enqueue > s:
                j = None
                continue
            lo = bisect.bisect_left(k_start, s - t0)
            hi = bisect.bisect_right(k_start, e - t0)
            kernels = sum(min(ke, e - t0) - ks for ks, ke in
                          self.trace.kernel_events[lo:hi])
            out.append((logs[j], e - s, kernels))
        return out

    def due_in_window(self) -> List[Served]:
        return [s for s in self.served
                if self.t_open <= s.t_due < self.t_close]

    def latencies_ms(self) -> List[float]:
        """Scheduled arrival to resolution of every document scheduled in
        the window that resolved."""
        return [1e3 * (s.t_done - s.t_due) for s in self.due_in_window()
                if s.t_done is not None]


def _drive(cell: Cell, server, handles, traffic, seconds: float, tracer):
    """The measured loop.  Returns (served, t_open, t_close).

    Closed loop: the window is a column job of ``traffic.window_docs``
    documents, whole blocks, so every seed does the same work: it opens
    as the first is submitted, keeps ``in_flight`` outstanding, and
    closes as the last resolves.  Open loop: the window is ``seconds``
    long after the ramp; documents scheduled in it are waited for."""
    mix = cell.mix
    served: Dict[Tuple[int, int], Served] = {}
    stream = traffic.stream()
    by_qid = {h.query_id: h for h in handles.values()}

    futures = {}

    def submit(doc, t_due: float) -> None:
        h = handles[(doc.tenant, doc.exit_stage)]
        now = time.perf_counter()
        futures[(h.query_id, doc.index)] = h.submit(
            doc.index, doc.text, arrival=t_due, arrival_ts=t_due)
        served[(h.query_id, doc.index)] = Served(doc, t_due, now)

    def poll() -> int:
        now = time.perf_counter()
        n = 0
        with span("bench.poll"):
            for qid, h in by_qid.items():
                for ext in h.poll():
                    s, f = served[(qid, ext)], futures[(qid, ext)]
                    s.t_done, s.status, s.error = now, f.status, f.error
                    if f.status == "resolved":
                        s.pred, s.conf = int(f.pred), float(f.conf)
                        s.exit_stage = int(f.exit_stage)
                    n += 1
        return n

    def step() -> None:
        with span("bench.step"):
            server.step()

    if mix["loop"] == "closed":
        n_flight, n_docs = int(mix["in_flight"]), traffic.window_docs(seconds)
        submitted = done = 0
        t_open = tracer.open(0.0)
        deadline = t_open + 3 * seconds + GRACE_S
        while done < n_docs and time.perf_counter() < deadline:
            with span("bench.submit"):
                while server.pending() < n_flight and submitted < n_docs:
                    submit(next(stream), time.perf_counter())
                    submitted += 1
            step()
            done += poll()
        t_close = time.perf_counter()
        tracer.close()
    else:
        ramp = float(mix["ramp_s"])
        t0 = time.perf_counter()
        t_open = t0 + ramp
        t_close = t_open + seconds
        nxt = next(stream)
        opened = False
        while True:
            now = time.perf_counter()
            if not opened and now >= t_open:
                tracer.open(max(seconds - float(mix["trace_s"]), 0.0))
                opened = True
            tracer.tick(now)
            if now >= t_close:
                tracer.close()
            with span("bench.submit"):
                while nxt is not None and t0 + nxt.arrival <= now:
                    submit(nxt, t0 + nxt.arrival)
                    nxt = next(stream)
                    if t0 + nxt.arrival >= t_close:
                        nxt = None
            if server.pending():
                step()
                poll()
            elif nxt is not None or now < t_close:
                until = t0 + nxt.arrival if nxt is not None else t_close
                with span("bench.wait"):
                    time.sleep(max(min(until - now, 0.05), 0.0))
            else:
                break
            if now > t_close + GRACE_S:
                break
        if tracer.active:
            tracer.close()
    # answers due in the window are waited for (a minute at most)
    deadline = time.perf_counter() + GRACE_S
    while server.pending() and time.perf_counter() < deadline:
        server.step()
        poll()
    return list(served.values()), t_open, t_close


def arena_sample(server) -> Tuple[int, float]:
    """(bytes reserved by every arena, bytes of KV the live documents'
    true tokens hold) at this instant."""
    reserved, live = 0, 0.0
    for be in server.backends.values():
        reserved += be.arena_nbytes()
        for d in be.live_docs():
            bucket = be._doc_slot[d][0]
            per_tok = be.slot_nbytes(bucket) / be._s_alloc_for(bucket)
            live += be.true_cached_len(d) * per_tok
    return reserved, live


class _Tracer:
    """Opens and closes the measured window: the compile count at its
    start and, in a traced run, the profiler from a given offset into the
    window to its close (stopping the profiler stalls the host, so it
    stops only as the window closes).  The profiler records device
    activity and the ``bench.*`` host spans; its Python tracer and HLO
    dumps are off, since they are what makes stopping it slow."""

    def __init__(self, enabled: bool, counter: Optional[CompileCounter]):
        self.enabled, self.active = enabled, False
        self.counter = counter
        self.lowered_at_open = 0
        self.start_at = float("inf")
        self.dir: Optional[str] = None
        self.span = (0.0, 0.0)
        self._window = None

    def lowered_in_window(self) -> int:
        return self.counter.lowered - self.lowered_at_open

    def open(self, trace_after: float) -> float:
        """The window opens now; trace from ``trace_after`` seconds on."""
        if self.counter is not None:
            self.lowered_at_open = self.counter.lowered
        now = time.perf_counter()
        self.start_at = now + trace_after
        self.tick(now)
        return now

    def tick(self, now: float) -> None:
        if self.enabled and self.dir is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = span("bench.window")
            self._window.__enter__()
            self.active = True
            now = time.perf_counter()
            self.span = (now, now)

    def close(self) -> None:
        if self.active:
            self._window.__exit__(None, None, None)
            self.span = (self.span[0], time.perf_counter())
            jax.profiler.stop_trace()
            self.active = False


def warm_up(cell: Cell, server, traffic, buckets: List[int]) -> int:
    """Run every launch signature and width the window can produce: for
    each bucket and width, that many documents through every stage (a
    query that never accepts before the oracle).  Returns launches run."""
    stages = [tuple(s) for s in cell.mix["stages"]]
    n_classes = int(cell.mix["classes"])
    h = server.register(routed_cascade(stages, len(stages), n_classes),
                        oracle_op=cell.mix["oracle_op"])
    k, futures = 0, []
    before = server.telemetry.launch_total
    for b in buckets:
        for w in widths(int(cell.config["serving"]["batch"])):
            for text in traffic.warm_docs(w, b):
                futures.append(h.submit(10**9 + k, text))
                k += 1
            h.drain()
    bad = [f for f in futures if f.status != "resolved"]
    if bad:
        print(f"bench: {len(bad)} warm-up documents ended {bad[0].status}: "
              f"{bad[0].error}", file=sys.stderr, flush=True)
    return server.telemetry.launch_total - before


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True,
        started: Optional[float] = None, log=None,
        cache: bool = True) -> Dict[str, Any]:
    """One run; returns the result line (a dict) and prints progress to
    ``log`` (stderr by default)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    started = started if started is not None else time.perf_counter()
    cell = load_cell(cell_name, root)
    dev = device_info(cell.chips, require_tpu)
    if cache:
        enable_cache(root)
    counter = CompileCounter()

    traffic = TR.Traffic(cell.mix, seed)
    buckets = traffic.buckets(bucket_len)
    n_due = traffic.window_docs(seconds)
    traffic.prefetch(n_due)
    server, params = build_server(cell, seed, buckets, traffic.operations())
    log(f"bench: {cell.name} seed {seed} on {dev['count']}x {dev['kind']}; "
        f"buckets {buckets}; built in {time.perf_counter() - started:.1f}s")
    warm = warm_up(cell, server, traffic, buckets)
    log(f"bench: warm-up ran {warm} launches; {counter.lowered} programs "
        f"lowered, {counter.compiled} compiled ({counter.compile_s:.1f}s)")

    stages = [tuple(s) for s in cell.mix["stages"]]
    n_classes = int(cell.mix["classes"])
    handles = {(t, e): server.register(routed_cascade(stages, e, n_classes),
                                       oracle_op=cell.mix["oracle_op"])
               for t in range(int(cell.mix["tenants"]))
               for e in range(len(stages) + 1)}
    launch_log = LaunchLog(server, cell, sample_arena=trace)
    tracer = _Tracer(trace, counter)
    served, t_open, t_close = _drive(cell, server, handles, traffic, seconds,
                                     tracer)
    in_window_compiles = tracer.lowered_in_window()
    setup_s = t_open - started
    recs = [r for r in server.telemetry.launches.items()
            if r.ok and t_open <= r.ts_start < t_close]
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    log(f"bench: set-up {setup_s:.1f}s; window {t_close - t_open:.3f}s; "
        f"{len(served)} of {n_due} documents submitted; "
        f"programs lowered in the window: {in_window_compiles}")

    summary = None
    if trace:
        summary = TRR.reduce_file(TRR.find_xplane(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        log(f"bench: trace: busy {summary.busy_s:.3f}s of "
            f"{summary.window_s:.3f}s on {summary.n_chips} chip(s)")
        for name, t in sorted(summary.modules_s.items(),
                              key=lambda kv: -kv[1])[:12]:
            log(f"  program {name}: {t:.4f}s")
        for name, t in summary.top_ops(12):
            log(f"  op {name}: {t:.4f}s")
        for name, t in summary.top_gaps(10):
            log(f"  idle while {name}: {t:.4f}s")

    r = Run(cell=cell, seconds=seconds, t_open=t_open, t_close=t_close,
            setup_s=setup_s, served=served, launches=recs,
            launch_log=launch_log.launches, trace=summary,
            trace_span=tracer.span,
            peaks=PK.peaks_for(dev["kind"]) if dev["platform"] == "tpu"
            else None)
    metrics: Dict[str, Any] = {}
    for m in (cell.per_layer if trace else cell.e2e):
        v = load_reader(root, m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- the output check, once the program's state is freed
    for s in [s for s in served if s.status != "resolved"][:5]:
        log(f"bench: document {s.doc.index} ended {s.status}: {s.error}")
    resolved = [s for s in served if s.status == "resolved"]
    unresolved = n_due - len(resolved)
    by_doc = {(s.doc.tenant, s.doc.index): s for s in resolved}
    sample = [by_doc[(d.tenant, d.index)]
              for d in traffic.sample([s.doc for s in resolved])]
    ops = traffic.operations()
    del server, handles
    gc.collect()
    checks = CK.compare(cell, params, resolved, sample, ops, log)
    checks["unresolved"] = {"value": unresolved, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct), "attempted": n_due,
           "failed": unresolved, "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.top_gaps(10)}
    out["checks"] = checks
    return out
