"""Overlapped ahead-of-time dispatch (ROADMAP item 2).

The server keeps up to ``inflight`` launches open: ``dispatch_group``
enqueues the jitted step non-blocking and returns a ticket whose
sanitizer bracket stays OPEN; ``complete_group`` syncs only when the
scheduler needs the launch's confidences for routing.  These tests pin
the contract: bitwise parity with ``inflight=1`` on the fault-free plane
(preds/confs/per-doc $ and arena device state), a seeded chaos drain
with K>1 under the sanitizer (all docs terminal, ledger exact, zero
violations), the sanitizer still catching two open tickets on one row,
faults surfacing at completion rather than dispatch, per-ticket timing
that never forces synchronization, and the step's refill after its
completion: one launch open at ``inflight=1`` while the caller is between
steps, a client loop bitwise equal to ``drain()``, no open ticket once
the queue empties, and ``early`` marking exactly the refilled launches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitizer import ArenaRaceError
from repro.config import resolve
from repro.configs import get_reduced
from repro.core.tasks import Cascade, Task, TaskConfig
from repro.data.documents import generate_corpus
from repro.data.tokenizer import HashWordTokenizer
from repro.models.model import LM
from repro.models.runtime import CPU_TEST
from repro.serving.engine import CascadeServer, LMBackend
from repro.serving.faults import (FaultInjector, FaultPlan,
                                  InjectedLaunchFailure)
from repro.serving.scheduler import (TERMINAL_STATES, RetryPolicy,
                                     bucket_len, fraction_len)


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

IMPOSSIBLE = {0: 2.0, 1: 2.0}
# multi-stage forced ladder: every doc escalates, so the queue always
# holds several same-signature cohorts — the overlap window fills
LADDER = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), IMPOSSIBLE),
    Task(TaskConfig("proxy", "o_orig", 1.0), IMPOSSIBLE),
])
CASCADE = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.7, 1: 0.7}),
    Task(TaskConfig("proxy", "o_orig", 1.0), {0: 0.7, 1: 0.7}),
])


@pytest.fixture(scope="module")
def tokz():
    return HashWordTokenizer(vocab_size=512)


@pytest.fixture(scope="module")
def docs():
    return {d.doc_id: d.text
            for d in generate_corpus(8, avg_lines=10, seed=7)}


def _capture_releases(backends):
    """Fingerprint every document's arena row at the moment it exits.

    Post-drain arena bytes are NOT schedule-comparable: dispatch order
    at K>1 legally differs from K=1 (the window fills with already-ready
    cohorts before a completion re-queues escalated docs), so doc->slot
    assignment permutes AND freed slots are reused in different orders,
    leaving schedule-dependent stale bytes past each new owner's valid
    region.  The schedule-independent contract is what a document LEAVES
    BEHIND: wrap ``release`` to snapshot the departing doc's valid KV
    window ``[0, cached_len)`` (its slot is still owned here, and
    eviction drains conflicting tickets before releasing, so no open
    ticket can be writing the row).  Returns the store, filled as
    ``(backend, bucket, doc) -> [(cached_len, true_len, bytes), ...]``
    (a list: an evicted doc releases once per preemption plus once at
    exit)."""
    store = {}
    for nm in sorted(backends):
        be = backends[nm]
        orig = be.release

        def release(doc_id, be=be, orig=orig, nm=nm):
            bs = be._doc_slot.get(doc_id)
            if bs is not None:
                bucket, slot = bs
                ar = be._arenas.get(bucket)
                if ar is not None:
                    c = int(ar.cached_len[slot])
                    t = int(ar.true_len[slot])
                    if c == 0:
                        body = b""
                    elif be.model.supports_paged_kv:
                        win = be.model.take_kv_window(
                            ar.states, jnp.asarray([slot], jnp.int32),
                            jnp.asarray([0], jnp.int32), c)
                        body = b"".join(np.asarray(leaf).tobytes()
                                        for leaf in jax.tree.leaves(win))
                    else:       # no seq-axis contract: full row, best-effort
                        flat, _ = jax.tree_util.tree_flatten_with_path(
                            ar.states)
                        body = b"".join(
                            np.take(np.asarray(leaf), slot,
                                    axis=ar.model._state_batch_axis(path)
                                    ).tobytes()
                            for path, leaf in flat)
                    store.setdefault((nm, bucket, doc_id), []).append(
                        (c, t, body))
            orig(doc_id)

        be.release = release
    return store


def _build(tokz, inflight, sanitize=None):
    """Fresh backends + server at the given window depth: (server,
    backends, release-time row fingerprints)."""
    backends = {"proxy": _mk_backend("proxy", 1, tokz, sanitize=sanitize),
                "oracle": _mk_backend("oracle", 2, tokz,
                                      sanitize=sanitize)}
    rows = _capture_releases(backends)
    srv = CascadeServer(dict(backends), OPS, n_classes=2, batch_size=4,
                        retry=RetryPolicy(max_retries=2, backoff_base=0.0),
                        inflight=inflight)
    return srv, backends, rows


def _submit_all(h, docs):
    for i, d in enumerate(sorted(docs)):
        h.submit(d, docs[d], arrival=float(i))


def _replay(tokz, docs, cascade, inflight, sanitize=None, plan=None):
    """Drain the whole corpus (logical arrivals) on a fresh server and
    return (server, result, backends, release-time row fingerprints)."""
    srv, backends, rows = _build(tokz, inflight, sanitize)
    if plan is not None:
        FaultInjector(plan).install(srv)
    h = srv.register(cascade)
    _submit_all(h, docs)
    res = h.drain()
    return srv, res, backends, rows


def _ledger_exact(srv):
    per_q = {qid: 0.0 for qid in srv._handles}
    per_d = {}
    for _, qid, rid, cost in srv.ledger():
        per_q[qid] += cost
        per_d[rid] = per_d.get(rid, 0.0) + cost
    assert all(total == srv.cost(qid) for qid, total in per_q.items())
    assert all(per_d.get(rid, 0.0) == req.cost
               for rid, req in srv._requests.items())


# --------------------------------------------------- bitwise parity K vs 1
def test_inflight_parity_bitwise(tokz, docs):
    """Ahead-of-time dispatch may only change WHEN the host blocks,
    never what it computes: preds, confs, per-doc $, and the arena row
    content every document leaves behind must equal the ``inflight=1``
    run bitwise — and the deep run must actually overlap
    (``max_inflight >= 2``)."""
    srv1, res1, bk1, rows1 = _replay(tokz, docs, LADDER, inflight=1)
    srv3, res3, bk3, rows3 = _replay(tokz, docs, LADDER, inflight=3)
    assert srv1._max_inflight_seen == 1
    assert srv3._max_inflight_seen >= 2
    assert res3.pred == res1.pred
    assert res3.conf == res1.conf           # float equality, not approx
    assert res3.doc_cost == res1.doc_cost
    assert res3.status == res1.status
    # dispatch order may legally differ (the window fills with ready
    # cohorts before completions re-queue escalated docs), but billing
    # must be the same per-document ENTRIES, reordered at most
    assert sorted((q, r, c) for _, q, r, c in srv3.ledger()) \
        == sorted((q, r, c) for _, q, r, c in srv1.ledger())
    _ledger_exact(srv3)
    assert rows1, "release capture never fired"
    assert set(rows3) == set(rows1)
    for key in rows1:                       # (backend, bucket, doc)
        assert rows3[key] == rows1[key], key   # (lens, KV bytes), bitwise
    snap = srv3.telemetry_snapshot()
    assert snap["server"]["max_inflight"] >= 2
    assert "overlap_hidden_frac" in snap["timeline"]
    assert "mean_launch_gap_ms" in snap["timeline"]
    assert "inflight_s" in snap["timeline"]


# ------------------------------------------- chaos drain, K>1, sanitized
def test_chaos_drain_inflight_sanitized(tokz, docs):
    """Seeded fault injection with three launches in flight under the
    arena sanitizer: every document terminal, billing ledger exact,
    zero sanitizer violations across the open-bracket windows."""
    plan = FaultPlan(seed=3, launch_failure_p=0.15, nan_p=0.1,
                     latency_spike_p=0.1, spike_s=1e-4, arena_loss_at=4)
    srv, res, backends, _ = _replay(tokz, docs, CASCADE, inflight=3,
                                    sanitize=True, plan=plan)
    assert all(s in TERMINAL_STATES for s in res.status.values())
    assert set(res.status) == set(docs)
    _ledger_exact(srv)
    sans = [be._sanitizer for be in backends.values()
            if be._sanitizer is not None]
    assert sans, "sanitizer never engaged"
    assert sum(s.violations for s in sans) == 0
    assert sum(s.checks for s in sans) > 0
    assert srv.faults.counts["arena_losses"] == 1


# ------------------------------------- sanitizer catches overlapping rows
def test_sanitizer_raises_on_shared_row_open_tickets(tokz, docs):
    """The open bracket is the audit surface: while a dispatched
    ticket's launch is un-completed, a second launch registering the
    same row must raise ``ArenaRaceError`` — and succeed again once the
    ticket completes."""
    be = _mk_backend("proxy", 1, tokz, sanitize=True)
    d = sorted(docs)[0]
    toks = {d: np.asarray(be.tokenizer.encode(docs[d]), np.int32)}
    bucket = bucket_len(len(toks[d]))
    f_len = fraction_len(bucket, 1.0)
    op = np.asarray(be.tokenizer.encode("test op"), np.int32)
    ticket = be.dispatch_group([d], toks, bucket, f_len, 1.0, 0, op, 2)
    assert ticket.san is not None
    _, row = be._doc_slot[d]
    with pytest.raises(ArenaRaceError) as exc:
        ticket.san.begin_launch(bucket, "deliberate-overlap",
                                reads={row}, writes={row})
    assert exc.value.kind == "overlap"
    assert row in exc.value.rows
    be.complete_group(ticket)               # closes the bracket
    t2 = ticket.san.begin_launch(bucket, "after-completion",
                                 reads={row}, writes={row})
    ticket.san.end_launch(t2)
    assert ticket.san.inflight_peak >= 1


# ------------------------------------------- faults surface at completion
def test_faults_surface_at_completion(tokz, docs):
    """A poisoned launch returns a ticket from ``dispatch_group``
    without touching the wrapped backend (nothing enqueued, no state
    committed); the ``InjectedLaunchFailure`` raises at
    ``complete_group`` — where async dispatch surfaces real device
    errors."""
    be = _mk_backend("proxy", 1, tokz)
    fb = FaultInjector(FaultPlan(seed=0, launch_failure_p=1.0)).wrap(be)
    d = sorted(docs)[0]
    toks = {d: np.asarray(be.tokenizer.encode(docs[d]), np.int32)}
    bucket = bucket_len(len(toks[d]))
    f_len = fraction_len(bucket, 1.0)
    op = np.asarray(be.tokenizer.encode("test op"), np.int32)
    ticket = fb.dispatch_group([d], toks, bucket, f_len, 1.0, 0, op, 2)
    assert ticket.inner is None             # step never enqueued
    assert be.cached_len(d) == 0            # no state committed
    with pytest.raises(InjectedLaunchFailure):
        fb.complete_group(ticket)


# --------------------------------------------------- per-ticket timing
def test_per_ticket_timing_without_sync(tokz, docs):
    """``dispatch_group`` must not block: the ticket carries only the
    host/dispatch segments until completion measures the sync wait;
    ``last_timing`` updates at completion (per-ticket, no forced
    sync inside the step)."""
    be = _mk_backend("proxy", 1, tokz)
    d = sorted(docs)[0]
    toks = {d: np.asarray(be.tokenizer.encode(docs[d]), np.int32)}
    bucket = bucket_len(len(toks[d]))
    f_len = fraction_len(bucket, 1.0)
    op = np.asarray(be.tokenizer.encode("test op"), np.int32)
    be.last_timing = None
    ticket = be.dispatch_group([d], toks, bucket, f_len, 1.0, 0, op, 2)
    assert set(ticket.timing) == {"host", "dispatch"}
    assert be.last_timing is None           # nothing synced yet
    assert ticket.ts_dispatched >= ticket.ts_enqueue > 0.0
    pred, conf, new_d, cached_d = be.complete_group(ticket)
    assert set(ticket.timing) == {"host", "dispatch", "sync"}
    assert be.last_timing == ticket.timing
    assert ticket.ts_ready >= ticket.ts_sync >= ticket.ts_dispatched
    assert len(pred) == len(conf) == 1
    assert int(new_d[0]) > 0


# ------------------------------------------ refill after the completion
def test_step_refills_one_launch_at_inflight_1(tokz, docs):
    """A step that completes a launch while another group is ready
    returns with exactly that one launch open: the device works while
    the caller is between steps, and never holds two launches."""
    srv, _, _ = _build(tokz, inflight=1)
    h = srv.register(LADDER)
    _submit_all(h, docs)                    # 8 documents, launches of 4
    srv.step()
    assert len(srv._flights) == 1 and srv._flights[0].early
    assert srv._max_inflight_seen == 1
    h.drain()
    assert srv._max_inflight_seen == 1


def test_client_loop_matches_drain_bitwise(tokz, docs):
    """The benchmark client's closed loop (keep three documents
    admitted, step, poll) refills between its submits, so it launches
    other groups than ``drain()``; preds, confs, per-document $ and the
    arena rows every document leaves behind are bitwise the same."""
    srv0, ref, _, rows0 = _replay(tokz, docs, LADDER, inflight=1)
    srv, _, rows = _build(tokz, inflight=1)
    h = srv.register(LADDER)
    order, submitted, polled = sorted(docs), 0, {}
    while len(polled) < len(order):
        while srv.pending() < 3 and submitted < len(order):
            d = order[submitted]
            h.submit(d, docs[d], arrival=float(submitted))
            submitted += 1
        srv.step()
        polled.update(h.poll())
    res = h.result()
    assert any(r.early for r in srv.telemetry.launches.items())
    assert srv._max_inflight_seen == 1
    assert polled == {d: (ref.pred[d], ref.conf[d], ref.exit_stage[d])
                      for d in order}
    assert res.pred == ref.pred
    assert res.conf == ref.conf             # float equality, not approx
    assert res.doc_cost == ref.doc_cost
    assert sorted((q, r, c) for _, q, r, c in srv.ledger()) \
        == sorted((q, r, c) for _, q, r, c in srv0.ledger())
    _ledger_exact(srv)
    assert set(rows) == set(rows0)
    for key in rows0:                       # (backend, bucket, doc)
        assert rows[key] == rows0[key], key


def test_last_step_leaves_no_open_ticket(tokz, docs):
    """Stepping until nothing is pending: every step but the last
    returns with the refilled launch open; the last finds the queue
    empty at its completion and leaves the device idle."""
    srv, _, _ = _build(tokz, inflight=1)
    h = srv.register(LADDER)
    _submit_all(h, docs)
    open_after = []
    while srv.pending():
        srv.step()
        open_after.append(len(srv._flights))
    assert open_after == [1] * (len(open_after) - 1) + [0]
    assert srv.pending() == 0 and h.pending() == 0
    assert len(open_after) == srv.telemetry.launch_total


@pytest.mark.parametrize("inflight", [1, 3])
def test_early_counts_only_refilled_launches(tokz, docs, inflight):
    """``LaunchRecord.early`` and ``serve_early_dispatch_total`` count
    exactly the launches dispatched after a completion inside the same
    step, as the backends saw them (dispatch and completion wrapped)."""
    srv, backends, _ = _build(tokz, inflight=inflight)
    seen = {"completed": False, "after": set()}
    for be in backends.values():
        dispatch, complete = be.dispatch_group, be.complete_group

        def dispatch_group(*a, _real=dispatch, **kw):
            if seen["completed"]:
                seen["after"].add(kw["launch"])
            return _real(*a, **kw)

        def complete_group(ticket, _real=complete):
            seen["completed"] = True
            return _real(ticket)

        be.dispatch_group, be.complete_group = dispatch_group, complete_group
    h = srv.register(LADDER)
    _submit_all(h, docs)
    while srv.pending():
        seen["completed"] = False
        srv.step()
    recs = srv.telemetry.launches.items()
    assert {r.index for r in recs if r.early} == seen["after"]
    assert 0 < len(seen["after"]) < len(recs)
    if inflight == 1:                       # only the first launch waits
        assert [r.early for r in recs] == [False] + [True] * (len(recs) - 1)
    reg = srv.telemetry.registry
    for name in backends:
        assert reg.counter("serve_early_dispatch_total",
                           backend=name).value \
            == sum(r.early for r in recs if r.model == name)
