"""Fault-tolerant serving plane: injection harness determinism, retry/
backoff isolation, deadlines, quarantine/escalation, circuit breaker,
arena-loss recovery, journal warm restart, watchdog, submit validation,
eviction-under-retry interplay, and exact $-accounting via the ledger."""
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
from repro.serving.engine import (CascadeServer, LMBackend, RequestJournal,
                                  ServerStalledError)
from repro.serving.faults import FaultInjector, FaultPlan
from repro.serving.scheduler import (FAILED, RESOLVED, TERMINAL_STATES,
                                     TIMED_OUT, RetryPolicy)


def _mk_backend(name, seed, tokz, **kw):
    cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                      num_layers=2)
    rcfg = resolve(cfg, tp=1)
    m = LM(rcfg, CPU_TEST)
    return LMBackend(
        name=name, model=m, params=m.init(jax.random.PRNGKey(seed)),
        tokenizer=tokz,
        rate_per_token=1.0 if name == "oracle" else 0.06, s_alloc=512, **kw)


OPS = {"o_orig": "does this overturn a lower court decision",
       "sur_1": "is a lower court mentioned"}

THR = {0: 0.7, 1: 0.7}
IMPOSSIBLE = {0: 2.0, 1: 2.0}

CASCADE = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), THR),
    Task(TaskConfig("proxy", "o_orig", 1.0), THR),
])
LADDER = Cascade([
    Task(TaskConfig("proxy", "o_orig", 0.25), IMPOSSIBLE),
    Task(TaskConfig("proxy", "o_orig", 1.0), IMPOSSIBLE),
])


@pytest.fixture(scope="module")
def backends():
    tokz = HashWordTokenizer(vocab_size=512)
    return {"proxy": _mk_backend("proxy", 1, tokz),
            "oracle": _mk_backend("oracle", 2, tokz)}


@pytest.fixture(scope="module")
def docs():
    return {d.doc_id: d.text
            for d in generate_corpus(8, avg_lines=10, seed=7)}


def mk_server(backends, **kw):
    for be in backends.values():
        be.reset()
    kw.setdefault("retry", RetryPolicy(max_retries=2, backoff_base=0.0))
    return CascadeServer(dict(backends), OPS, n_classes=2, batch_size=4,
                         **kw)


def _assert_ledger_exact(srv):
    """Replaying the billing ledger per query reproduces cost(qid)
    EXACTLY (same float additions in the same order)."""
    per_q = {qid: 0.0 for qid in srv._handles}
    for _, qid, _, cost in srv.ledger():
        per_q[qid] += cost
    for qid, total in per_q.items():
        assert total == srv.cost(qid)


# ---------------------------------------------------------------- injector

def test_injector_schedule_is_seed_deterministic():
    plan = FaultPlan(seed=5, launch_failure_p=0.3, nan_p=0.2,
                     latency_spike_p=0.1)
    a, b = FaultInjector(plan), FaultInjector(plan)
    assert [a.draw() for _ in range(64)] == [b.draw() for _ in range(64)]
    assert a.calls == 64


def test_faulty_backend_forwards_attributes(backends):
    inj = FaultInjector(FaultPlan(seed=0))
    proxy = inj.wrap(backends["proxy"])
    assert proxy.name == "proxy"
    assert proxy.rate_per_token == backends["proxy"].rate_per_token
    proxy.host_overhead_s = 1.25           # setattr forwards to the inner
    assert backends["proxy"].host_overhead_s == 1.25
    backends["proxy"].host_overhead_s = 0.0


# ---------------------------------------------------------- submit checks

def test_submit_validation(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    with pytest.raises(ValueError, match="empty or"):
        h.submit(0, "")
    with pytest.raises(ValueError, match="empty or"):
        h.submit(0, "  \n\t ")
    text = next(iter(docs.values()))
    h.submit(0, text)
    with pytest.raises(ValueError, match="already submitted"):
        h.submit(0, text)
    h2 = srv.register(CASCADE)
    h2.submit(0, text)              # doc ids are scoped per query
    srv.drain()


# ------------------------------------------------- launch failure + retry

def test_failed_launch_retries_solo_and_resolves(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=3, launch_failure_p=1.0))
    inj.install(srv)
    futs = [h.submit(d, docs[d], arrival=float(i))
            for i, d in enumerate(sorted(docs)[:3])]
    assert srv.step() == []                 # packed launch fails
    # the step's refill already dispatched the first solo retry, drawn
    # while the backend still fails; it surfaces at the next completion
    assert inj.counts["launch_failures"] == 2
    assert h.stats.retries == 3             # every member re-enqueued
    assert all(not f.done for f in futs)    # ... but nobody failed
    assert [len(f.launch.doc_ids) for f in srv._flights] == [1]
    inj.plan = FaultPlan(seed=3)            # heal the backend
    # poisoned-cohort isolation: survivors retry in SINGLETON groups
    launch = srv._queue.next_launch(srv._stage_of, srv.batch_size)
    assert len(launch.doc_ids) == 1
    srv._queue.push(srv._requests[launch.doc_ids[0]])
    res = h.drain()
    assert all(f.status == RESOLVED for f in futs)
    assert set(res.pred) == {d for d in sorted(docs)[:3]}
    _assert_ledger_exact(srv)


def test_retries_exhausted_resolves_failed(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=1, launch_failure_p=1.0))
    inj.install(srv)
    futs = [h.submit(d, docs[d]) for d in sorted(docs)[:2]]
    res = h.drain()                         # terminates, never hangs
    assert all(f.done and f.status == FAILED for f in futs)
    assert all("launch failed" in f.error for f in futs)
    assert h.stats.failures == 2
    assert res.pred == {}                   # no RESOLVED documents
    assert set(res.status.values()) == {FAILED}
    assert srv.stats().breaker_trips >= 1   # persistent failures trip it
    with pytest.raises(RuntimeError, match="failed"):
        futs[0].result()


def test_deadline_resolves_timed_out(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    d0, d1 = sorted(docs)[:2]
    late = h.submit(d0, docs[d0], deadline_s=0.0)     # expires immediately
    ok = h.submit(d1, docs[d1])
    res = h.drain()
    assert late.status == TIMED_OUT and late.error == "deadline exceeded"
    assert ok.status == RESOLVED
    assert h.stats.timeouts == 1
    assert res.status[d0] == TIMED_OUT and d0 not in res.pred
    with pytest.raises(RuntimeError, match="timed_out"):
        late.result()


# ------------------------------------------------------------- quarantine

def test_nan_quarantine_retries_solo_then_resolves(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=2, nan_p=1.0))
    inj.install(srv)
    d0 = sorted(docs)[0]
    fut = h.submit(d0, docs[d0])
    srv.step()                              # NaN conf -> quarantined
    assert h.stats.quarantines == 1 and not fut.done
    inj.plan = FaultPlan(seed=2)            # heal
    h.drain()
    assert fut.status == RESOLVED
    _assert_ledger_exact(srv)               # the NaN launch is still billed


def test_persistent_nan_escalates_then_fails(backends, docs):
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=2, nan_p=1.0))
    inj.install(srv)
    d0 = sorted(docs)[0]
    fut = h.submit(d0, docs[d0])
    srv.step()                              # quarantine 1: solo retry,
    #                                         refilled while still NaN
    inj.plan = FaultPlan(seed=2)            # oracle now healthy
    srv.step()                              # quarantine 2: escalate to final
    final = len(h.stages) - 1
    assert srv._requests[srv._ids[(h.query_id, d0)]].stage == final
    h.drain()
    assert fut.status == RESOLVED and fut.exit_stage == final
    # and with the oracle ALSO emitting NaN, the document fails cleanly
    srv2 = mk_server(backends)
    h2 = srv2.register(CASCADE)
    FaultInjector(FaultPlan(seed=2, nan_p=1.0)).install(srv2)
    fut2 = h2.submit(d0, docs[d0])
    h2.drain()
    assert fut2.status == FAILED
    assert "non-finite" in fut2.error
    assert h2.stats.quarantines == 3


# -------------------------------------------------------- circuit breaker

def test_breaker_reroutes_sick_backend_to_next_stage(backends, docs):
    srv = mk_server(backends, breaker_threshold=2, breaker_cooldown=64,
                    retry=RetryPolicy(max_retries=3, backoff_base=0.0))
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=4, launch_failure_p=1.0))
    srv.backends["proxy"] = inj.wrap(srv.backends["proxy"])   # proxy only
    futs = [h.submit(d, docs[d]) for d in sorted(docs)[:4]]
    res = h.drain()
    final = len(h.stages) - 1
    assert all(f.status == RESOLVED for f in futs)
    assert all(s == final for s in res.exit_stage.values())   # via oracle
    assert h.stats.breaker_trips >= 1
    assert srv.stats().breaker_trips == srv._breaker_trips
    # the sick backend's stages were BILLED as the oracle stage
    assert res.stats.stage_cost[final] > 0
    _assert_ledger_exact(srv)


# ------------------------------------------------------------- arena loss

def test_arena_loss_replays_eviction_and_rebills_prefill(backends, docs):
    sub = {d: docs[d] for d in sorted(docs)[:4]}
    srv = mk_server(backends)
    h = srv.register(LADDER)
    for i, d in enumerate(sorted(sub)):
        h.submit(d, sub[d], arrival=float(i))
    clean = h.drain()
    assert srv.stats().recovered_docs == 0
    cost_clean = srv.cost(h.query_id)

    srv2 = mk_server(backends)
    h2 = srv2.register(LADDER)
    inj = FaultInjector(FaultPlan(seed=9, arena_loss_at=1))
    inj.install(srv2)
    futs = [h2.submit(d, sub[d], arrival=float(i))
            for i, d in enumerate(sorted(sub))]
    res = h2.drain()
    assert inj.counts["arena_losses"] == 1
    assert h2.stats.recovered_docs > 0
    assert all(f.status == RESOLVED for f in futs)
    assert res.pred == clean.pred           # recovery changes $, not answers
    # survivors re-prefilled from scratch: the lost cache is re-billed
    assert srv2.cost(h2.query_id) > cost_clean
    _assert_ledger_exact(srv2)


# -------------------------------------------------------- journal restart

def test_journal_recovery_restores_and_resubmits(backends, docs):
    srv = mk_server(backends, journal=RequestJournal())
    h = srv.register(CASCADE)
    sub = sorted(docs)[:6]
    for i, d in enumerate(sub):
        h.submit(d, docs[d], arrival=float(i))
    def _done():
        return {d: (srv._requests[srv._ids[(h.query_id, d)]].pred,
                    srv._requests[srv._ids[(h.query_id, d)]].cost)
                for d in sub
                if srv._requests[srv._ids[(h.query_id, d)]].done}

    while not _done():                      # partial progress, then "crash"
        srv.step()
    journal = srv.journal
    done_before = _done()
    assert 0 < len(done_before) < len(sub)  # some resolved, some not

    srv2 = mk_server(backends, journal=RequestJournal())
    h2 = srv2.register(CASCADE)
    futs = srv2.recover(journal)
    assert set(d for _, d in futs) == set(sub)
    for d, (pred, cost) in done_before.items():
        fut = futs[(h2.query_id, d)]
        assert fut.done and fut.pred == pred and fut.cost == cost
    assert h2.stats.recovered_docs == len(sub) - len(done_before)
    res = h2.drain()
    assert all(futs[(h2.query_id, d)].status in TERMINAL_STATES
               for d in sub)
    assert set(res.status) == set(sub)
    _assert_ledger_exact(srv2)
    # the new server's OWN journal is complete: a second crash recovers too
    assert len(srv2.journal.unresolved()) == 0


# --------------------------------------------------------------- watchdog

def test_watchdog_raises_on_stall_with_stuck_listing(backends, docs):
    srv = mk_server(backends, stall_limit=5)
    h = srv.register(CASCADE)
    d0 = sorted(docs)[0]
    fut = h.submit(d0, docs[d0])
    srv._requests[srv._ids[(h.query_id, d0)]].not_before = math.inf
    with pytest.raises(ServerStalledError) as ei:
        srv.drain()
    assert ei.value.stuck == [(h.query_id, d0, 0, 0, math.inf)]
    assert not fut.done


def test_finite_backoff_is_not_a_stall(backends, docs):
    srv = mk_server(backends, stall_limit=2,
                    retry=RetryPolicy(max_retries=2, backoff_base=0.01,
                                      backoff_cap=0.01))
    h = srv.register(CASCADE)
    inj = FaultInjector(FaultPlan(seed=6, launch_failure_p=1.0))
    inj.install(srv)
    fut = h.submit(sorted(docs)[0], docs[sorted(docs)[0]])
    h.drain()                               # sleeps out backoffs, no stall
    assert fut.status == FAILED


# ------------------------------------------------- eviction under retry

def test_eviction_during_backoff_rebills_prefill_once():
    """A's third proxy stage fails, B evicts A during the retry, and A's
    re-prefill of that stage is billed once.  Each step refills the
    window after its completion, so the launch a plan poisons is the one
    refilled in the step after the plan is set."""
    ladder = Cascade([
        Task(TaskConfig("proxy", "o_orig", 0.25), IMPOSSIBLE),
        Task(TaskConfig("proxy", "o_orig", 0.5), IMPOSSIBLE),
        Task(TaskConfig("proxy", "o_orig", 1.0), IMPOSSIBLE),
    ])
    tokz = HashWordTokenizer(vocab_size=512)
    bks = {"proxy": _mk_backend("proxy", 1, tokz, slot_budget=1),
           "oracle": _mk_backend("oracle", 2, tokz)}
    srv = CascadeServer(bks, OPS, n_classes=2, batch_size=4,
                        retry=RetryPolicy(max_retries=2, backoff_base=0.0))
    corpus = {d.doc_id: d.text
              for d in generate_corpus(2, avg_lines=10, seed=11)}
    da, db = sorted(corpus)
    ha = srv.register(ladder)
    hb = srv.register(ladder)
    fa = ha.submit(da, corpus[da], arrival=0.0)
    srv.step()                              # A runs stage 0, caches f=0.25
    rid = srv._ids[(ha.query_id, da)]
    assert srv._requests[rid].cached["proxy"] > 0
    inj = FaultInjector(FaultPlan(seed=8, launch_failure_p=1.0))
    inj.install(srv)
    srv.step()                              # A's stage 1; stage 2 poisoned
    assert srv._requests[rid].retries == 0
    inj.plan = FaultPlan(seed=8)            # heal
    # B arrives with priority; A's stage-2 launch fails at completion and
    # the refill launches B, which evicts A (slot_budget=1) mid-retry
    fb = hb.submit(db, corpus[db], arrival=-1.0)
    srv.step()
    assert srv._requests[rid].retries == 1
    assert srv._requests[rid].evictions == 1
    assert srv._requests[rid].cached["proxy"] == 0
    assert srv._requests[rid].retries == 1  # eviction preserves retry count
    srv.drain()
    assert fa.status == RESOLVED and fb.status == RESOLVED
    # A's stage-2 re-prefill was billed exactly once: the full document
    # (cache lost) plus the op suffix, as NEW tokens
    toks_a = len(tokz.encode(corpus[da]))
    op_len = len(tokz.encode(OPS["o_orig"]))
    assert ha.stats.stage_new_tokens[2] == toks_a + op_len
    assert ha.stats.stage_cached_tokens[2] == 0
    assert ha.stats.retries == 1 and ha.stats.evictions == 1
    _assert_ledger_exact(srv)


# ----------------------------------------------- fault-free path is inert

def test_fault_free_path_matches_pre_fault_engine(backends, docs):
    """With no injector, no deadlines, and default policies, the new
    control flow adds nothing: results and $ match a plain run."""
    srv = mk_server(backends)
    h = srv.register(CASCADE)
    for i, d in enumerate(sorted(docs)):
        h.submit(d, docs[d], arrival=float(i))
    res = h.drain()
    st = h.stats
    assert st.retries == st.quarantines == st.timeouts == 0
    assert st.failures == st.breaker_trips == st.recovered_docs == 0
    assert set(res.status.values()) == {RESOLVED}
    assert srv._stalled_steps == 0
    _assert_ledger_exact(srv)
