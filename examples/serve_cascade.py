"""Full data-plane integration: real documents, real JAX models, a task
cascade built FROM engine scores and executed BY the engine.

    PYTHONPATH=src python examples/serve_cascade.py

Pipeline (mirrors Figure 2 of the paper, end to end on CPU):
  1. generate a synthetic text corpus with planted relevance;
  2. fit the §4 document restructurer (oracle line ranges -> granularity ->
     JAX relevance classifier) and reorder every document;
  3. evaluate candidate task configs (2 models x 2 operations x fractions)
     by running the proxy/oracle LMs through the serving engine on the dev
     split — confidences come off the LM heads' class tokens;
  4. Alg 2 thresholds + Alg 4 greedy assembly over those scores;
  5. serve the test split MULTI-TENANT: one ``CascadeServer`` owns the
     backends, arenas, and the global request queue, and two registered
     queries (the assembled cascade plus a strict-threshold variant of
     it) stream the same feed concurrently through the
     register -> submit -> step/poll -> result lifecycle.  Documents from
     both queries that share a static launch signature merge into ONE
     launch (cross-query packing over shared KV arenas); per-query
     latency (p50/p99), cost vs oracle-only, and cache hit rate come out
     of each handle's own stats;
  6. replay the same feed under INJECTED FAULTS (seeded launch failures,
     NaN confidences, one arena loss) to show the failure model: every
     document still reaches a terminal state — RESOLVED, FAILED, or
     TIMED_OUT — via solo retries with backoff, non-finite-confidence
     quarantine (solo retry, then escalate to the final stage), and
     eviction-path arena recovery; then crash the server mid-flight and
     warm-restart a fresh one from its write-ahead request journal;
  7. re-serve the cascade on PREFIX-SHARING bf16 arenas: each operation
     prefix prefills once per (backend, op, bucket) into a pinned shared
     arena row aliased by every document's block table, and the KV
     stores at half an f32 row (``kv_dtype='bfloat16'``) — more live
     documents per byte of HBM, same billing contract;
  8. record a Perfetto trace of a two-tenant chaos run (span events,
     launch timeline, metric registry);
  9. gate the tree with the RSA linter (``python -m repro.analysis``)
     and replay the chaos feed under the runtime ARENA SANITIZER
     (``ARENA_SANITIZE=1`` / ``LMBackend.sanitize=True``): every
     launch's read/write row sets are bracketed, so slot-aliasing
     races raise ``ArenaRaceError`` instead of corrupting KV;
 10. re-serve the feed with OVERLAPPED AHEAD-OF-TIME DISPATCH
     (``inflight=4``): ``step()`` enqueues up to four jitted launches
     before blocking, syncing a ticket only when the scheduler needs
     its confidences for routing — preds/confs/$ stay bitwise those of
     the depth-1 run while the device-wait drops behind the in-flight
     window (the printed overlap-hidden fraction).

The data plane underneath is PAGED on Pallas runtimes: each document owns
one slot row of a persistent per-bucket KV arena, the per-launch slot ids
ride into the kernels through scalar-prefetch SMEM, and decode/extend read
``k_arena[slot]`` blocks in place — no [B, S] gather copy per launch (the
demo's CPU runtime uses the bitwise-identical gather reference plane; see
``serving/engine.py``).

Models are tiny untrained LMs (this is a mechanics/integration demo —
"accuracy" is agreement with the oracle MODEL, exactly the paper's alpha
definition).
"""
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.config import resolve
from repro.configs import get_reduced
from repro.core.assembly import greedy_assembly
from repro.core.cost_model import CascadeCostModel
from repro.core.restructure import DocumentRestructurer, SyntheticOracle
from repro.core.tasks import Cascade, TaskConfig, TaskScores, run_cascade
from repro.core.thresholds import filter_tasks
from repro.data.documents import generate_corpus
from repro.data.tokenizer import HashWordTokenizer
from repro.launch.serve import poisson_arrivals, warm_arena
from repro.models.model import LM
from repro.models.runtime import CPU_TEST
from repro.serving.engine import (CascadeEngine, CascadeServer, LMBackend,
                                  RequestJournal)
from repro.serving.faults import FaultInjector, FaultPlan
from repro.serving.scheduler import RESOLVED, RetryPolicy
from repro.serving.telemetry import write_chrome_trace

OPS = {
    "o_orig": "does this opinion overturn a lower court decision",
    "sur_court": "is any lower court mentioned overturn reversed vacated",
    "sur_affirm": "does it say affirmed upheld sustained",
}
FRACTIONS = (0.25, 1.0)


def main():
    t0 = time.time()
    print("1. corpus + restructuring")
    docs = generate_corpus(28, n_classes=2, avg_lines=16, seed=11)
    restr = DocumentRestructurer(OPS["o_orig"]).fit(
        docs[:12], SyntheticOracle(noise=0.1))
    reordered = {d.doc_id: restr.reorder(d).text for d in docs}
    dev_ids = [d.doc_id for d in docs[:12]]
    test_ids = [d.doc_id for d in docs[12:]]
    print(f"   granularity={restr.granularity} lines, "
          f"classifier F1={restr.f1:.2f}")

    print("2. backends (tiny untrained proxy + oracle LMs)")
    tokz = HashWordTokenizer(vocab_size=512)

    def mk(name, arch, seed, rate):
        cfg = get_reduced(arch, dtype="float32", vocab_size=512,
                          num_layers=2)
        m = LM(resolve(cfg, tp=1), CPU_TEST)
        return LMBackend(name=name, model=m,
                         params=m.init(jax.random.PRNGKey(seed)),
                         tokenizer=tokz, rate_per_token=rate, s_alloc=1024)

    backends = {"proxy": mk("proxy", "llama3_2_1b", 1, 0.15e-6),
                "oracle": mk("oracle", "qwen3_1_7b", 2, 2.50e-6)}
    engine = CascadeEngine(backends, OPS, n_classes=2, batch_size=4)

    print("3. candidate evaluation on the dev split (engine-backed)")
    dev_docs = {i: reordered[i] for i in dev_ids}
    # oracle reference predictions (the alpha target)
    oracle_ref = engine.run(Cascade([]), dev_docs)
    oracle_pred = np.asarray([oracle_ref.pred[i] for i in dev_ids])

    configs = [TaskConfig(m, o, f)
               for m in ("proxy",) for o in OPS for f in FRACTIONS
               if not (o == "o_orig" and f == 1.0 and m == "oracle")]
    scores = {}
    for cfg in configs:
        # direct single-stage scoring: run one stage with no thresholds
        be = engine.backends[cfg.model]
        be.reset()
        import math
        toks = {i: np.asarray(be.tokenizer.encode(dev_docs[i]), np.int32)
                for i in dev_ids}
        from repro.serving.scheduler import make_buckets
        lens = {i: len(toks[i]) for i in dev_ids}
        pred = np.zeros(len(dev_ids), np.int64)
        conf = np.zeros(len(dev_ids))
        pos = {i: k for k, i in enumerate(dev_ids)}
        for blen, ids in make_buckets(dev_ids, lens, 4):
            p, c, *_ = be.run_stage(
                ids, toks, blen, cfg.fraction,
                np.asarray(be.tokenizer.encode(OPS[cfg.operation]),
                           np.int32), 2)
            for j, d in enumerate(ids):
                pred[pos[d]], conf[pos[d]] = p[j], c[j]
        scores[cfg] = TaskScores(cfg, pred, conf)
    doc_tokens = np.asarray(
        [len(tokz.encode(reordered[i])) for i in dev_ids])
    cm = CascadeCostModel(doc_tokens, {o: len(tokz.encode(t))
                                       for o, t in OPS.items()},
                          rates={"proxy": 0.15e-6, "oracle": 2.50e-6})

    print("4. Alg 2 thresholds + Alg 4 greedy assembly")
    eligible = filter_tasks(list(scores.values()), oracle_pred, 2,
                            alpha=0.85, g=0.10)
    cascade, trace = greedy_assembly(eligible, scores, oracle_pred, cm, 2,
                                     alpha=0.85)
    print(f"   eligible tasks: {len(eligible)}; assembled: "
          f"{[t.config.key() for t in cascade.tasks]}")

    print("5. multi-tenant serving: two queries, one CascadeServer")
    # Data plane: every document holds one slot row of a persistent
    # per-bucket KV arena; launches address rows by slot id.  On Pallas
    # runtimes the ids ride in scalar-prefetch SMEM and the kernels DMA
    # arena blocks in place (paged attention — zero row-copy bytes per
    # decode launch); this CPU demo uses the gather reference plane,
    # which is bitwise-identical by construction.
    test_docs = {i: reordered[i] for i in test_ids}
    # a second tenant: the same task configs under stricter thresholds —
    # distinct query, yet every launch signature (and compiled step, and
    # arena slot pool) is shared with the first
    strict = cascade.with_thresholds([
        {c: min(v + 0.10, 1.0) for c, v in t.thresholds.items()}
        for t in cascade.tasks])
    # the engine doubles as the server's warm-up driver: compile every
    # launch signature streaming can produce before the timed session
    warm_arena(engine, cascade, test_docs, engine.batch_size)

    # lifecycle: (a) register each query -> QueryHandle ...
    server = engine            # a CascadeEngine IS a CascadeServer
    server.reset()
    h_main = server.register(cascade, accuracy_target=0.85)
    h_strict = server.register(strict, accuracy_target=0.95)
    print(f"   registered query {h_main.query_id} (alpha>=0.85) and query "
          f"{h_strict.query_id} (alpha>=0.95) on one server")

    # ... (b) submit each tenant's feed (same docs, no id collision —
    # document ids are scoped per query) ...
    arrivals = poisson_arrivals(sorted(test_docs), rate=8.0, seed=3)
    wall0 = time.perf_counter()
    for d in sorted(test_docs):
        h_main.submit(d, test_docs[d], arrival=arrivals[d])
        h_strict.submit(d, test_docs[d], arrival=arrivals[d])

    # ... (c) step the shared queue and poll each handle for ITS results
    polled = {h_main.query_id: {}, h_strict.query_id: {}}
    while server.pending():
        server.step()
        for h in (h_main, h_strict):
            polled[h.query_id].update(h.poll())
    wall = time.perf_counter() - wall0
    res, res_strict = h_main.result(), h_strict.result()
    assert polled[h_main.query_id].keys() == res.pred.keys()
    occupancy, launches = server.occupancy(), server.stats().batches

    # engine.run() below resets the server session (the results/stats
    # captured above stay valid — they are materialized per query)
    oracle_only = engine.run(Cascade([]), test_docs)
    agree = np.mean([res.pred[i] == oracle_only.pred[i] for i in test_ids])
    stats = res.stats
    print(f"   served 2x{len(test_ids)} docs in {wall:.1f}s; "
          f"occupancy {occupancy:.2f} docs/launch")
    print(f"   query {h_main.query_id}: latency "
          f"p50 {1e3 * stats.latency_quantile(0.5):.0f} ms / "
          f"p99 {1e3 * stats.latency_quantile(0.99):.0f} ms; "
          f"cost ${res.cost * 1e3:.4f}m vs oracle-only "
          f"${oracle_only.cost * 1e3:.4f}m "
          f"({res.cost / oracle_only.cost:.2f}x)")
    print(f"   query {h_strict.query_id} (strict): cost "
          f"${res_strict.cost * 1e3:.4f}m; oracle fall-through "
          f"{np.mean([s == len(strict.tasks) for s in res_strict.exit_stage.values()]):.0%}"
          f" vs {np.mean([s == len(cascade.tasks) for s in res.exit_stage.values()]):.0%}")
    print(f"   agreement with oracle: {agree:.1%}; "
          f"KV cache hit rate {stats.cache_hit_rate():.1%}; "
          f"launches {launches}")
    print("6. failure model: injected faults, terminal states, warm restart")
    # The serving plane guarantees every submitted document reaches a
    # TERMINAL state (RESOLVED / FAILED / TIMED_OUT) under launch
    # failures (failed launches re-enqueue members solo with backoff),
    # non-finite confidences (quarantine: solo retry, then escalate to
    # the final stage), sick backends (circuit breaker routes around
    # them), and arena loss (slots released, documents re-prefill via
    # the eviction path).  backoff_base=0.0 keeps the replay instant and
    # the launch schedule a pure function of the chaos seed.
    for be in backends.values():
        be.reset()
    chaos = CascadeServer(backends, OPS, n_classes=2, batch_size=4,
                          retry=RetryPolicy(max_retries=2, backoff_base=0.0),
                          journal=RequestJournal())
    h_chaos = chaos.register(cascade)
    inj = FaultInjector(FaultPlan(seed=5, launch_failure_p=0.25, nan_p=0.2,
                                  arena_loss_at=3)).install(chaos)
    feed = sorted(test_docs)[:8]
    for k, d in enumerate(feed):
        h_chaos.submit(d, test_docs[d], arrival=float(k))
    # "crash" the server after a few steps: the write-ahead journal has
    # every submission, so a FRESH server re-registers the same query and
    # recovers — resolved docs restore verbatim (no re-execution, $ carried
    # over), in-flight docs are resubmitted from their original arrivals.
    for _ in range(4):
        chaos.step()
    crashed_journal = chaos.journal
    print(f"   pre-crash: {len(crashed_journal.resolutions)} of {len(feed)} "
          f"docs terminal after 4 steps under injected faults "
          f"({inj.counts['launch_failures']} launch failures, "
          f"{inj.counts['nan_confidences']} NaN confidences, "
          f"{inj.counts['arena_losses']} arena losses)")
    for be in backends.values():
        be.reset()
    warm = CascadeServer(backends, OPS, n_classes=2, batch_size=4,
                         retry=RetryPolicy(max_retries=2, backoff_base=0.0),
                         journal=RequestJournal())
    warm.register(cascade)
    FaultInjector(FaultPlan(seed=5, nan_p=0.2)).install(warm)
    futures = warm.recover(crashed_journal)
    warm.drain()
    statuses = [f.status for f in futures.values()]
    chaos_stats = warm.stats()
    print(f"   recovered server: {len(futures)} docs -> "
          f"{sum(s == RESOLVED for s in statuses)} RESOLVED, "
          f"{sum(s != RESOLVED for s in statuses)} FAILED/TIMED_OUT; "
          f"retries={chaos_stats.retries} "
          f"quarantines={chaos_stats.quarantines} "
          f"recovered_docs={chaos_stats.recovered_docs} "
          f"(every submitted doc is terminal: "
          f"{all(f.done for f in futures.values())})")

    print("7. prefix sharing + bf16 arenas: more live docs per HBM byte")
    # The op-first plane (``prefix_sharing=True``) prefills each
    # operation's tokens ONCE per (backend, op, bucket) into a pinned
    # shared arena row; every document's block table aliases it (COW on
    # ragged remainders), so the per-document prefill shrinks by the op
    # length.  ``kv_dtype='bfloat16'`` stores the arena at half an f32
    # row, dequantized at read.  Billing follows the token-accounting
    # contract, not the physical work: on same-op fraction ladders the $
    # is EXACTLY the doc-before-op plane's (an op SWITCH re-prefills, by
    # construction — the doc's KV attends to the op prefix).
    def mk_shared(name, arch, seed, rate, kv_dtype="bfloat16"):
        cfg = get_reduced(arch, dtype="float32", vocab_size=512,
                          num_layers=2)
        m = LM(resolve(cfg, tp=1), CPU_TEST)
        return LMBackend(name=name, model=m,
                         params=m.init(jax.random.PRNGKey(seed)),
                         tokenizer=tokz, rate_per_token=rate, s_alloc=1024,
                         prefix_sharing=True, kv_dtype=kv_dtype)

    shared_be = {"proxy": mk_shared("proxy", "llama3_2_1b", 1, 0.15e-6),
                 "oracle": mk_shared("oracle", "qwen3_1_7b", 2, 2.50e-6)}
    shared_eng = CascadeEngine(shared_be, OPS, n_classes=2, batch_size=4)
    res_shared = shared_eng.run(cascade, test_docs)
    sst = res_shared.stats
    bucket = 1024
    # same-geometry comparison: prefix sharing rounds the row length to a
    # block multiple, so the f32 reference row must share that layout
    probe_f32 = mk_shared("proxy", "llama3_2_1b", 1, 0.15e-6, kv_dtype=None)
    b_f32 = probe_f32.slot_nbytes(bucket)
    b_bf16 = shared_be["proxy"].slot_nbytes(bucket)
    assert b_bf16 == b_f32 // 2                 # stored dtype is billed
    assert sst.prefix_hits > 0                  # docs aliased shared rows
    print(f"   prefix_hits={sst.prefix_hits} cow_copies={sst.cow_copies} "
          f"arena_bytes_peak={sst.arena_bytes_peak / 1e6:.1f}MB; "
          f"slot row {b_f32 / 1e6:.2f}MB f32 -> {b_bf16 / 1e6:.2f}MB bf16")
    print(f"   cost ${res_shared.cost * 1e3:.4f}m vs f32 private "
          f"${res.cost * 1e3:.4f}m (same-op ladders bill identically; "
          f"this cascade's op switches re-prefill)")

    print("8. telemetry: Perfetto trace of a two-tenant chaos run")
    # Telemetry is on by default at level="counters" (metric registry +
    # launch timeline, bitwise inert to the data plane); level="trace"
    # additionally records per-document span events — submit, every
    # launch ridden, escalations, retries, injected faults, quarantine,
    # the terminal state — into a bounded ring.  The Chrome trace-event
    # export lays launches (with their sched/host/dispatch/sync
    # segments) on per-backend tracks and doc spans on per-query tracks.
    for be in backends.values():
        be.reset()
    traced = CascadeServer(backends, OPS, n_classes=2, batch_size=4,
                           retry=RetryPolicy(max_retries=2,
                                             backoff_base=0.0))
    traced.telemetry.level = "trace"
    FaultInjector(FaultPlan(seed=5, launch_failure_p=0.25, nan_p=0.2,
                            arena_loss_at=3)).install(traced)
    t_main = traced.register(cascade)
    t_strict = traced.register(strict)
    for k, d in enumerate(feed):
        t_main.submit(d, test_docs[d], arrival=float(k))
        t_strict.submit(d, test_docs[d], arrival=float(k))
    traced.drain()
    snap = traced.telemetry_snapshot()
    tl = snap["timeline"]
    trace_path = "serve_trace.json"
    write_chrome_trace(traced.telemetry, trace_path)
    print(f"   {snap['counters']['events_total']} span events over "
          f"{snap['spans']['checked']} doc spans, "
          f"{snap['counters']['launch_records']} launch records "
          f"({snap['counters']['failed_launch_records']} failed); "
          f"spans well-formed: {snap['spans']['ok']}")
    print(f"   wall decomposition: sched {1e3 * tl['sched_s']:.1f} ms | "
          f"host {1e3 * tl['host_s']:.1f} ms | dispatch "
          f"{1e3 * tl['dispatch_s']:.1f} ms | sync "
          f"{1e3 * tl['sync_s']:.1f} ms; mean launch gap "
          f"{tl['mean_launch_gap_ms']:.2f} ms")
    print(f"   wrote {trace_path} — open at https://ui.perfetto.dev "
          f"(one track per backend with launch+segment slices, one per "
          f"query with per-document span slices)")

    print("9. static analysis + sanitized chaos drain")
    # The repo-specific AST linter (rules RSA001-RSA005: jit signature
    # hygiene, Pallas conventions, donation safety, merge metadata,
    # wall-clock/RNG in jit — catalogue in ``repro.analysis.__doc__``)
    # gates the tree against the committed suppression baseline, and the
    # runtime arena sanitizer replays the chaos feed with every launch's
    # read/write row sets bracketed: slot-aliasing races, pinned-prefix
    # writes outside COW, and use-after-release raise ``ArenaRaceError``
    # instead of corrupting KV silently.  The sanitizer is host-side
    # shadow state only — preds/confs/$ are bitwise those of step 6.
    from repro.analysis import lint as rsa_lint
    rc = rsa_lint.main(["src/repro"])
    assert rc == 0, "linter found new violations (see output above)"
    for be in backends.values():
        be.reset()
        be.sanitize = True          # or ARENA_SANITIZE=1 in the env
        be._sanitizer = None
    sane = CascadeServer(backends, OPS, n_classes=2, batch_size=4,
                         retry=RetryPolicy(max_retries=2,
                                           backoff_base=0.0))
    FaultInjector(FaultPlan(seed=5, launch_failure_p=0.25, nan_p=0.2,
                            arena_loss_at=3)).install(sane)
    s_main = sane.register(cascade)
    for k, d in enumerate(feed):
        s_main.submit(d, test_docs[d], arrival=float(k))
    sane.drain()
    sans = [b._sanitizer for b in backends.values()
            if b._sanitizer is not None]
    checks = sum(s.checks for s in sans)
    assert checks > 0 and sum(s.violations for s in sans) == 0
    print(f"   linter clean vs baseline; sanitized chaos drain: "
          f"{checks} launch brackets, "
          f"{sum(s.rows_checked for s in sans)} row memberships, "
          f"0 violations")
    for be in backends.values():
        be.sanitize = None          # leave the demo backends env-driven

    print("10. overlapped dispatch: four launches in flight")
    # ``dispatch_group`` enqueues the jitted stage step WITHOUT blocking
    # (JAX async dispatch) and returns a ticket; the completion loop
    # calls ``block_until_ready`` only when the scheduler needs that
    # launch's confidences for stage routing.  Depth may only change
    # WHEN the host blocks, never what it computes — so the whole feed
    # replays bitwise against step 5's query while the gap between
    # consecutive enqueues collapses.
    overlap_res = {}
    for depth in (1, 4):
        for be in backends.values():
            be.reset()
        deep = CascadeServer(backends, OPS, n_classes=2, batch_size=4,
                             inflight=depth)
        h_deep = deep.register(cascade)
        for d in sorted(test_docs):
            h_deep.submit(d, test_docs[d], arrival=arrivals[d])
        deep.drain()
        overlap_res[depth] = (h_deep.result(), deep.telemetry_snapshot())
    r1, (rk, snapk) = overlap_res[1][0], overlap_res[4]
    assert rk.pred == r1.pred and rk.conf == r1.conf
    assert rk.doc_cost == r1.doc_cost
    tl1, tlk = overlap_res[1][1]["timeline"], snapk["timeline"]
    print(f"   max_inflight={snapk['server']['max_inflight']} "
          f"(window 4); preds/confs/$ bitwise equal to inflight=1")
    print(f"   overlap-hidden fraction "
          f"{tl1['overlap_hidden_frac']:.1%} -> "
          f"{tlk['overlap_hidden_frac']:.1%}; mean launch gap "
          f"{tl1['mean_launch_gap_ms']:.2f} ms -> "
          f"{tlk['mean_launch_gap_ms']:.2f} ms")

    print(f"done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
