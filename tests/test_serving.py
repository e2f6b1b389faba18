"""Serving: multi-tenant server (cross-query packing, per-query
partitioning), request loop, prefix reuse, slot/byte budgets + eviction,
cost parity, scheduler buckets + ready queue + policies."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ATTN_FULL, MAMBA2, resolve
from repro.configs import get_reduced
from repro.core.tasks import Cascade, Task, TaskConfig
from repro.data.documents import generate_corpus
from repro.data.tokenizer import HashWordTokenizer
from repro.models.model import LM
from repro.models.runtime import CPU_TEST
from repro.serving.engine import CascadeEngine, CascadeServer, LMBackend
from repro.serving.scheduler import (DocRequest, RequestQueue, ServeStats,
                                     bucket_len, largest_ready_group,
                                     make_buckets, pack_stage_batches)


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


def _mk_engine(batch_size=4, **backend_kw):
    tokz = HashWordTokenizer(vocab_size=512)
    backends = {"proxy": _mk_backend("proxy", 1, tokz, **backend_kw),
                "oracle": _mk_backend("oracle", 2, tokz, **backend_kw)}
    return CascadeEngine(backends, OPS, n_classes=2, batch_size=batch_size)


@pytest.fixture(scope="module")
def engine():
    return _mk_engine()


@pytest.fixture(scope="module")
def docs():
    return {d.doc_id: d.text
            for d in generate_corpus(10, avg_lines=10, seed=7)}


def test_engine_resolves_every_doc(engine, docs):
    cascade = Cascade([
        Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.7, 1: 0.7}),
        Task(TaskConfig("proxy", "o_orig", 1.0), {0: 0.7, 1: 0.7}),
    ])
    res = engine.run(cascade, docs)
    assert set(res.pred) == set(docs)
    assert all(0 <= s <= 2 for s in res.exit_stage.values())
    assert res.cost > 0


def test_engine_prefix_reuse_reduces_cost(engine, docs):
    """fraction ladder 0.25 -> 1.0 on the same model must hit the cache."""
    thr = {0: 2.0, 1: 2.0}     # impossible thresholds: nothing exits early
    ladder = Cascade([
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 1.0), thr),
    ])
    res = engine.run(ladder, docs)
    assert res.stats.cache_hit_rate() > 0.05
    # cached tokens ~= the 0.25 prefix re-read at stage 2
    assert res.stats.stage_cached_tokens[1] > 0


def test_engine_extension_equals_fresh(engine, docs):
    """Same doc, fraction 0.25 then 1.0 == fresh 1.0 (logit-exact)."""
    be = engine.backends["proxy"]
    be.reset()
    d0 = next(iter(docs))
    toks = {d0: np.asarray(be.tokenizer.encode(docs[d0]), np.int32)}
    blen = bucket_len(len(toks[d0]))
    op = np.asarray(be.tokenizer.encode("test op"), np.int32)
    be.run_stage([d0], toks, blen, 0.25, op, 2)
    _, c_ext, *_ = be.run_stage([d0], toks, blen, 1.0, op, 2)
    be.reset()
    _, c_fresh, *_ = be.run_stage([d0], toks, blen, 1.0, op, 2)
    np.testing.assert_allclose(c_ext, c_fresh, atol=1e-5)


def test_engine_smaller_fraction_reuses_larger_cache(engine, docs):
    """After f=1.0 is cached, f=0.5 must be fully cached (no new doc toks)."""
    be = engine.backends["proxy"]
    be.reset()
    d0 = next(iter(docs))
    toks = {d0: np.asarray(be.tokenizer.encode(docs[d0]), np.int32)}
    blen = bucket_len(len(toks[d0]))
    op = np.asarray(be.tokenizer.encode("op"), np.int32)
    be.run_stage([d0], toks, blen, 1.0, op, 2)
    _, _, new_t, cached_t = be.run_stage([d0], toks, blen, 0.5, op, 2)
    assert new_t == len(op)            # only operation tokens are new
    assert cached_t > 0


def test_mixed_entry_stages_reuse_cached_prefixes(engine, docs):
    """Docs that enter the cascade at different stages share a bucket but
    keep their cached prefixes: the stage splits into per-cached-len
    launches instead of re-prefilling the whole batch (the seed fallback).
    """
    thr = {0: 2.0, 1: 2.0}     # impossible: nothing exits before the oracle
    ladder = Cascade([
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 1.0), thr),
    ])
    late = sorted(docs)[0]
    res = engine.run(ladder, docs, enter_stage={late: 1})
    # stage 1 mixes veterans (cached at f=0.25) with the late entrant
    # (cached_len 0); veterans' prefixes must be billed as cached
    assert res.stats.stage_cached_tokens[1] > 0
    # the late entrant only ever runs stages 1 and 2
    assert res.stats.stage_docs[0] == len(docs) - 1
    assert res.stats.stage_docs[1] == len(docs)
    assert set(res.pred) == set(docs)


def test_run_stage_heterogeneous_cache_matches_homogeneous(engine, docs):
    """A mixed-cache batch returns the same confidences as separate runs."""
    be = engine.backends["proxy"]
    ids = sorted(docs)[:2]
    toks = {d: np.asarray(be.tokenizer.encode(docs[d]), np.int32)
            for d in ids}
    blen = max(bucket_len(len(t)) for t in toks.values())
    op = np.asarray(be.tokenizer.encode("mixed op"), np.int32)
    # homogeneous reference: each doc alone, fresh, straight to f=1.0
    be.reset()
    _, c0, *_ = be.run_stage([ids[0]], toks, blen, 1.0, op, 2)
    _, c1, *_ = be.run_stage([ids[1]], toks, blen, 1.0, op, 2)
    # mixed: doc0 pre-cached at 0.25, doc1 cold, one batched call
    be.reset()
    be.run_stage([ids[0]], toks, blen, 0.25, op, 2)
    _, c_mix, new_t, cached_t = be.run_stage(ids, toks, blen, 1.0, op, 2)
    assert cached_t > 0                       # doc0's prefix was reused
    np.testing.assert_allclose(c_mix, [c0[0], c1[0]], atol=1e-5)


def test_slot_recycling(engine, docs):
    """Released slots are re-issued before the arena grows."""
    be = engine.backends["proxy"]
    be.reset()
    ids = sorted(docs)[:3]
    toks = {d: np.asarray(be.tokenizer.encode(docs[d]), np.int32)
            for d in ids}
    blen = max(bucket_len(len(t)) for t in toks.values())
    op = np.asarray(be.tokenizer.encode("op"), np.int32)
    be.run_stage(ids[:2], toks, blen, 1.0, op, 2)
    assert be._alloc.high_water(blen) == 2
    be.release(ids[0])
    be.run_stage([ids[2]], toks, blen, 1.0, op, 2)
    assert be._alloc.high_water(blen) == 2    # reused the freed slot
    assert be.cached_len(ids[2]) == max(int(np.ceil(blen)), 1)


def test_engine_stage_cost_exposed(engine, docs):
    cascade = Cascade([
        Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.7, 1: 0.7}),
    ])
    res = engine.run(cascade, docs)
    assert res.stage_cost == res.stats.stage_cost
    assert res.cost == pytest.approx(sum(res.stage_cost))
    assert res.cost == pytest.approx(res.stats.total_cost())
    assert all(c >= 0 for c in res.stage_cost)


def test_pack_stage_batches_groups_by_cached_len():
    lengths = {1: 30, 2: 30, 3: 30, 4: 100}
    cached = {1: 8, 2: 8, 3: 0, 4: 0}
    batches = pack_stage_batches([1, 2, 3, 4], lengths, cached,
                                 fraction=1.0, batch_size=8)
    keys = {(b.bucket, b.cached_len): list(b.doc_ids) for b in batches}
    assert keys == {(32, 8): [1, 2], (32, 0): [3], (128, 0): [4]}
    # caches covering the fraction collapse into one decode-only group
    batches = pack_stage_batches([1, 2, 3], lengths,
                                 {1: 32, 2: 16, 3: 32},
                                 fraction=0.25, batch_size=8)
    assert [(b.bucket, b.cached_len, b.doc_ids) for b in batches] == \
        [(32, 8, (1, 2, 3))]


def test_bucketing():
    assert bucket_len(10) == 32
    assert bucket_len(33) == 64
    lengths = {i: l for i, l in enumerate([10, 20, 40, 50, 60, 500])}
    batches = make_buckets(range(6), lengths, batch_size=2)
    sizes = [blen for blen, _ in batches]
    assert sizes == sorted(sizes)
    all_ids = [d for _, ids in batches for d in ids]
    assert sorted(all_ids) == list(range(6))
    assert all(len(ids) <= 2 for _, ids in batches)


def test_serve_stats_accounting():
    s = ServeStats()
    s.record(0, 4, 100, 0)
    s.record(1, 2, 50, 30)
    assert s.total_new_tokens() == 150
    assert s.total_cached_tokens() == 30
    assert 0 < s.cache_hit_rate() < 1
    s.latencies = [0.1, 0.2, 0.3, 0.4]
    assert s.latency_quantile(0.5) == pytest.approx(0.25)
    assert s.latency_quantile(1.0) == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# Continuous-batching request loop
# ---------------------------------------------------------------------------

LADDER = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.7, 1: 0.7}),
    Task(TaskConfig("proxy", "o_orig", 1.0), {0: 0.75, 1: 0.75}),
])


def test_request_loop_matches_run(engine, docs):
    """run() is a thin wrapper over submit()/step()/poll()/drain(): driving
    the loop by hand must produce identical preds/confs/cost."""
    ref = engine.run(LADDER, docs)

    engine.start(LADDER)
    for i, (d, text) in enumerate(docs.items()):
        engine.submit(d, text, arrival=float(i))
    polled = {}
    while engine.pending():
        engine.step()
        polled.update(engine.poll())
    res = engine.result()
    assert res.pred == ref.pred
    assert res.exit_stage == ref.exit_stage
    assert res.conf == ref.conf                      # bit-identical
    assert res.cost == pytest.approx(ref.cost, rel=1e-12)
    assert res.stats.stage_docs == ref.stats.stage_docs
    assert res.stats.total_new_tokens() == ref.stats.total_new_tokens()
    assert res.stats.total_cached_tokens() == ref.stats.total_cached_tokens()
    # poll() delivered every resolution exactly once
    assert {d: v[0] for d, v in polled.items()} == ref.pred
    assert len(res.stats.latencies) == len(docs)


def test_streaming_admission_mid_cascade(engine, docs):
    """Late arrivals are admitted between launches (not at stage barriers)
    and do not force veterans to re-prefill."""
    ids = sorted(docs)
    early, late = ids[: len(ids) // 2], ids[len(ids) // 2:]
    ref = engine.run(LADDER, docs)                    # static baseline

    engine.start(LADDER)
    for d in early:
        engine.submit(d, docs[d], arrival=0.0)
    # a few launches with only the early cohort in flight
    for _ in range(2):
        engine.step()
    mid_pending = engine.pending()
    for d in late:
        engine.submit(d, docs[d], arrival=1.0)
    assert engine.pending() > mid_pending             # admitted mid-run
    res = engine.drain()
    assert set(res.pred) == set(docs)
    assert res.pred == ref.pred
    # identical per-document token work: no whole-batch re-prefill happened
    assert res.stats.total_new_tokens() == ref.stats.total_new_tokens()
    assert res.stats.total_cached_tokens() == ref.stats.total_cached_tokens()
    assert res.stats.cache_hit_rate() >= ref.stats.cache_hit_rate()


def test_eviction_requeues_and_resolves(docs):
    """Under a tiny slot budget the newest-arrival slot is preempted; the
    evicted document re-resolves correctly with its re-prefill counted as
    new tokens."""
    ids = sorted(docs)[:2]
    sub = {d: docs[d] for d in ids}
    thr = {0: 2.0, 1: 2.0}                            # nothing exits early
    ladder = Cascade([
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 1.0), thr),
    ])
    ref_eng = _mk_engine(batch_size=1)
    ref = ref_eng.run(ladder, sub)                    # unbudgeted baseline

    eng = _mk_engine(batch_size=1, slot_budget=1)
    a, b = ids
    eng.start(ladder)
    eng.submit(a, sub[a], arrival=0.0)
    eng.step()                                        # a cached at stage 0
    assert eng.backends["proxy"].cached_len(a) > 0
    eng.submit(b, sub[b], arrival=-1.0)               # older -> higher prio
    eng.step()                                        # launches b, evicts a
    assert eng._stats.evictions >= 1
    assert eng.backends["proxy"].cached_len(a) == 0   # cache gone
    res = eng.drain()
    assert set(res.pred) == {a, b}
    assert res.pred == ref.pred
    np.testing.assert_allclose(
        [res.conf[d] for d in ids], [ref.conf[d] for d in ids], atol=1e-5)
    # the evicted doc's re-prefill is billed as new tokens
    assert res.stats.total_new_tokens() > ref.stats.total_new_tokens()
    assert res.stats.evictions == eng._reqs[a].evictions >= 1


def test_byte_budget_evicts_and_resolves(docs):
    """A byte-denominated budget preempts slots when the pending launch
    would GROW an arena past it; the evicted document re-resolves and the
    arenas never exceed the budget."""
    ids = _same_bucket_ids(docs, 2)
    sub = {d: docs[d] for d in ids}
    thr = {0: 2.0, 1: 2.0}
    ladder = Cascade([
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 1.0), thr),
    ])
    ref = _mk_engine(batch_size=1).run(ladder, sub)   # unbudgeted baseline

    eng = _mk_engine(batch_size=1, init_slots=1)
    bucket = bucket_len(
        len(eng.backends["proxy"].tokenizer.encode(sub[ids[0]])))
    for be in eng.backends.values():
        # room for ONE live row + the scratch row, never a second slot
        be.byte_budget = 2 * be.slot_nbytes(bucket)
        assert be.slot_budget is None                 # bytes bind, not slots
    a, b = ids
    eng.start(ladder)
    eng.submit(a, sub[a], arrival=0.0)
    eng.step()                                        # a cached at stage 0
    assert eng.backends["proxy"].cached_len(a) > 0
    eng.submit(b, sub[b], arrival=-1.0)               # older -> higher prio
    eng.step()                                        # b launches, evicts a
    assert eng._stats.evictions >= 1
    be = eng.backends["proxy"]
    assert be.cached_len(a) == 0                      # cache gone
    # an arena irreducibly over budget must NOT thrash its residents:
    # with no growth forced, same-bucket eviction frees no bytes
    live, saved = be.live_docs(), be.byte_budget
    assert live
    be.byte_budget = 1                                # below even one row
    assert be.evict_for_room(bucket, 0, live) == []   # need_new == 0
    assert be.live_docs() == live
    be.byte_budget = saved
    res = eng.drain()
    assert res.pred == ref.pred
    np.testing.assert_allclose(
        [res.conf[d] for d in ids], [ref.conf[d] for d in ids], atol=1e-5)
    # re-prefill billed as new tokens; arenas stayed within budget
    assert res.stats.total_new_tokens() > ref.stats.total_new_tokens()
    for be in eng.backends.values():
        assert be.arena_nbytes() <= be.byte_budget


def test_slot_nbytes_matches_arena_accounting(engine, docs):
    """The shape-only per-slot projection agrees exactly with the bytes a
    materialized arena pins."""
    be = engine.backends["proxy"]
    be.reset()
    d0 = sorted(docs)[0]
    toks = {d0: np.asarray(be.tokenizer.encode(docs[d0]), np.int32)}
    blen = bucket_len(len(toks[d0]))
    op = np.asarray(be.tokenizer.encode("op"), np.int32)
    be.run_stage([d0], toks, blen, 1.0, op, 2)
    ar = be._arenas[blen]
    assert be.slot_nbytes(blen) * (ar.capacity + 1) == ar.nbytes()
    assert be.projected_nbytes(blen, 0) == be.arena_nbytes()


def test_victim_order_prefers_fewest_cached_tokens(docs):
    """Eviction victims are ordered fewest-cached-tokens-lost first, with
    newest arrival breaking ties (the old policy was newest-only)."""
    eng = _mk_engine(batch_size=1)
    be = eng.backends["proxy"]
    a, b, c = sorted(docs)[:3]
    toks = {a: np.asarray(be.tokenizer.encode(docs[a]), np.int32),
            b: np.asarray(be.tokenizer.encode(docs[b]), np.int32)}
    toks[c] = toks[b]              # equal lengths -> equal cache: tie-break
    blen = max(bucket_len(len(t)) for t in toks.values())
    op = np.asarray(be.tokenizer.encode("op"), np.int32)
    be.run_stage([a], toks, blen, 0.25, op, 2)        # a: small cache, old
    be.run_stage([b, c], toks, blen, 1.0, op, 2)      # b, c: full caches
    eng._requests.update({
        a: DocRequest(a, arrival=0.0, seq=0),
        b: DocRequest(b, arrival=1.0, seq=1),
        c: DocRequest(c, arrival=2.0, seq=2),
    })
    # fewest cached tokens first (a, despite being OLDEST); among the
    # equal-cache pair, the newer arrival (c) goes first
    assert eng._victim_order(be, protected=set()) == [a, c, b]
    assert eng._victim_order(be, protected={a}) == [c, b]


def test_bucket_retirement_frees_arena():
    """A bucket idle for ``retire_after`` launches releases its arena."""
    eng = _mk_engine(batch_size=4, retire_after=1)
    short = "alpha beta gamma delta"
    long = " ".join(f"w{i} token" for i in range(60))
    eng.start(Cascade([]))                            # oracle-only resolve
    eng.submit(1, short, arrival=0.0)
    eng.submit(2, long, arrival=1.0)
    eng.step()                                        # short doc resolves
    oracle = eng.backends["oracle"]
    assert oracle.arena_nbytes() >= 0
    res = eng.drain()                                 # long doc's launch sees
    assert set(res.pred) == {1, 2}                    # the idle small bucket
    assert res.stats.retired_buckets >= 1
    small = bucket_len(len(oracle.tokenizer.encode(short)))
    assert small not in oracle._arenas                # device arena freed


# ---------------------------------------------------------------------------
# Multi-tenant server
# ---------------------------------------------------------------------------

def _same_bucket_ids(docs, n=2):
    """First ``n`` doc ids sharing one length bucket (largest such group)."""
    tokz = HashWordTokenizer(vocab_size=512)
    by_bucket = {}
    for d in sorted(docs):
        by_bucket.setdefault(
            bucket_len(len(tokz.encode(docs[d]))), []).append(d)
    ids = max(by_bucket.values(), key=len)
    assert len(ids) >= n, "corpus fixture lost its bucket overlap"
    return ids[:n]


QUERY_A = Cascade([
    Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.7, 1: 0.7}),
    Task(TaskConfig("proxy", "o_orig", 1.0), {0: 0.75, 1: 0.75}),
])
QUERY_B = Cascade([                        # same stage-0 signature as A,
    Task(TaskConfig("proxy", "sur_1", 0.25), {0: 0.9, 1: 0.9}),
    Task(TaskConfig("proxy", "sur_1", 1.0), {0: 0.8, 1: 0.8}),
])                                         # different thresholds + stage 1


def test_cross_query_packing_merges_launches(engine, docs):
    """Two registered queries whose stages share a (backend, bucket,
    cached_len, op, f_len) signature merge into ONE launch, with
    per-query preds/confs/$ identical to isolated engines."""
    ids = _same_bucket_ids(docs, 2)
    sub = {d: docs[d] for d in ids}
    ref_a = engine.run(QUERY_A, sub)                  # isolated baselines
    ref_b = engine.run(QUERY_B, sub)

    server = CascadeServer(engine.backends, OPS, n_classes=2, batch_size=8)
    server.reset()
    ha, hb = server.register(QUERY_A), server.register(QUERY_B)
    for j, d in enumerate(ids):
        ha.submit(d, sub[d], arrival=float(j))
        hb.submit(d, sub[d], arrival=float(j))
    server.step()
    # ONE launch carried stage-0 documents of BOTH queries
    assert server.stats().batches == 1
    assert server.stats(ha.query_id).batches == 1
    assert server.stats(hb.query_id).batches == 1
    assert server.stats(ha.query_id).stage_docs[0] == len(ids)
    assert server.stats(hb.query_id).stage_docs[0] == len(ids)

    while server.pending():
        server.step()
    res_a, res_b = ha.result(), hb.result()
    for res, ref in ((res_a, ref_a), (res_b, ref_b)):
        assert res.pred == ref.pred
        assert res.exit_stage == ref.exit_stage
        assert res.doc_cost == ref.doc_cost           # exact $ per document
        np.testing.assert_allclose(
            [res.conf[d] for d in ids], [ref.conf[d] for d in ids],
            atol=1e-6)
    # fewer launches than the two isolated sessions needed
    assert server.stats().batches \
        < ref_a.stats.batches + ref_b.stats.batches


def test_server_partitions_results_and_stats(engine, docs):
    """Doc ids are scoped per query; results, stats, and $ stay
    partitioned while the aggregate view counts each launch once."""
    ids = sorted(docs)[:4]
    sub = {d: docs[d] for d in ids}
    server = CascadeServer(engine.backends, OPS, n_classes=2, batch_size=4)
    server.reset()
    ha, hb = server.register(QUERY_A), server.register(QUERY_B)
    futs = [ha.submit(d, sub[d], arrival=float(j))
            for j, d in enumerate(ids)]
    for j, d in enumerate(ids):                       # same ids, no clash
        hb.submit(d, sub[d], arrival=float(j))
    polled_a = {}
    while server.pending():
        server.step()
        polled_a.update(ha.poll())
    res_a, res_b = ha.result(), hb.result()
    assert set(res_a.pred) == set(ids) == set(res_b.pred)
    assert polled_a == {d: (res_a.pred[d], res_a.conf[d],
                            res_a.exit_stage[d]) for d in ids}
    assert all(f.done and f.pred == res_a.pred[f.doc_id] for f in futs)
    assert res_a.cost == pytest.approx(sum(res_a.doc_cost.values()))
    # aggregate = per-query sums, but launches counted once
    agg = server.stats()
    assert sum(agg.stage_docs) == (sum(res_a.stats.stage_docs)
                                   + sum(res_b.stats.stage_docs))
    assert agg.batches < res_a.stats.batches + res_b.stats.batches
    assert server.occupancy() == pytest.approx(
        sum(agg.stage_docs) / agg.batches)
    assert agg.total_cost() == pytest.approx(res_a.cost + res_b.cost)
    # unregister frees one query's bookkeeping, the other survives, and
    # the server-wide launch history / packing metric do not shrink
    server.unregister(ha)
    assert ha.query_id not in server._handles
    assert hb.query_id in server._handles
    assert set(server.result(hb.query_id).pred) == set(ids)
    after = server.stats()
    assert after.batches == agg.batches
    assert sum(after.stage_docs) == sum(agg.stage_docs)
    assert server.occupancy() == pytest.approx(
        sum(agg.stage_docs) / agg.batches)


def test_doc_future_resolves(engine, docs):
    """handle.submit returns a DocFuture whose result() steps the server
    until that document resolves."""
    d0 = sorted(docs)[0]
    server = CascadeServer(engine.backends, OPS, n_classes=2, batch_size=4)
    server.reset()
    h = server.register(QUERY_A)
    fut = h.submit(d0, docs[d0])
    assert not fut.done
    pred, conf, stage = fut.result()
    assert fut.done and fut.pred == pred and fut.conf == conf
    assert fut.cost > 0
    assert h.result().pred == {d0: pred}


def test_engine_is_single_query_server(engine, docs):
    """The compatibility wrapper serves exactly one registered query and
    its results equal the server-API view of that query."""
    sub = {d: docs[d] for d in sorted(docs)[:3]}
    res = engine.run(LADDER, sub)
    assert set(res.doc_cost) == set(sub)
    assert res.cost == pytest.approx(sum(res.doc_cost.values()))
    assert engine.occupancy() == pytest.approx(
        sum(res.stats.stage_docs) / res.stats.batches)


def test_request_queue_head_of_line():
    """next_launch groups by static signature across stages and pops the
    group whose head request is oldest."""
    cfg = {0: ("proxy", "op_a", 0.25), 1: ("proxy", "op_b", 1.0)}
    q = RequestQueue()
    # veteran at stage 1 (oldest), two fresh arrivals at stage 0
    q.push(DocRequest(1, stage=1, arrival=0.0, seq=0,
                      tok_len={"proxy": 30}, cached={"proxy": 8}))
    q.push(DocRequest(2, stage=0, arrival=1.0, seq=1,
                      tok_len={"proxy": 30}))
    q.push(DocRequest(3, stage=0, arrival=2.0, seq=2,
                      tok_len={"proxy": 30}))
    first = q.next_launch(lambda r: cfg[r.stage], batch_size=8)
    assert first.doc_ids == (1,)                      # veteran first
    assert (first.op_id, first.cached_len, first.f_len) == ("op_b", 8, 32)
    second = q.next_launch(lambda r: cfg[r.stage], batch_size=8)
    assert second.doc_ids == (2, 3)                   # arrivals batched
    assert (second.op_id, second.cached_len) == ("op_a", 0)
    assert q.next_launch(lambda r: cfg[r.stage], batch_size=8) is None


def test_request_queue_merges_same_signature_across_stages():
    """Docs at different stage cursors with the same static signature share
    one launch (the stage index is bookkeeping, not a compiled shape)."""
    cfg = {0: ("proxy", "op_a", 1.0), 1: ("proxy", "op_a", 1.0)}
    q = RequestQueue()
    q.push(DocRequest(1, stage=1, arrival=0.0, seq=0, tok_len={"proxy": 20}))
    q.push(DocRequest(2, stage=0, arrival=1.0, seq=1, tok_len={"proxy": 20}))
    launch = q.next_launch(lambda r: cfg[r.stage], batch_size=8)
    assert launch.doc_ids == (1, 2)
    assert launch.stages == (1, 0)


def test_request_queue_merges_across_queries():
    """Requests from DIFFERENT queries (and different stages) share one
    launch when the per-query stage resolver lands them on the same static
    signature — the query id is bookkeeping, not a compiled shape."""
    cfgs = {0: {0: ("proxy", "op_a", 0.25)},
            1: {0: ("proxy", "op_x", 1.0), 1: ("proxy", "op_a", 0.25)}}
    q = RequestQueue()
    q.push(DocRequest(1, stage=0, arrival=0.0, seq=0, query_id=0,
                      tok_len={"proxy": 20}))
    q.push(DocRequest(2, stage=1, arrival=1.0, seq=1, query_id=1,
                      tok_len={"proxy": 20}))
    launch = q.next_launch(lambda r: cfgs[r.query_id][r.stage], batch_size=8)
    assert launch.doc_ids == (1, 2)                   # one mixed launch
    assert launch.op_id == "op_a"


def test_request_queue_largest_ready_group_policy():
    """policy=largest_ready_group picks the fullest group even when a
    smaller group holds the oldest head."""
    cfg = {0: ("proxy", "op_a", 1.0)}
    lone, pair = DocRequest(1, arrival=0.0, seq=0, tok_len={"proxy": 20}), [
        DocRequest(2, arrival=1.0, seq=1, tok_len={"proxy": 100}),
        DocRequest(3, arrival=2.0, seq=2, tok_len={"proxy": 100})]
    q = RequestQueue()
    for r in [lone] + pair:
        q.push(r)
    first = q.next_launch(lambda r: cfg[r.stage], batch_size=8,
                          policy=largest_ready_group)
    assert first.doc_ids == (2, 3)                    # fullest group wins
    second = q.next_launch(lambda r: cfg[r.stage], batch_size=8,
                           policy=largest_ready_group)
    assert second.doc_ids == (1,)
    # the default policy would have served the oldest head first
    for r in [lone] + pair:
        q.push(DocRequest(r.doc_id, arrival=r.arrival, seq=r.seq,
                          tok_len=dict(r.tok_len)))
    assert q.next_launch(lambda r: cfg[r.stage], batch_size=8).doc_ids \
        == (1,)


# ---------------------------------------------------------------------------
# Paged data plane: in-kernel slot lookup vs the gather/scatter stage step
# ---------------------------------------------------------------------------

from repro.models.runtime import Runtime  # noqa: E402

_PAGED_RT = Runtime(attn_impl="pallas_interpret", block_q=16, block_kv=16,
                    remat=False)


def _mk_paged_engine(paged, batch_size=4):
    """Two engines differing ONLY in the data plane: paged vs gather."""
    tokz = HashWordTokenizer(vocab_size=512)

    def be(name, seed):
        cfg = get_reduced("llama3_2_1b", dtype="float32", vocab_size=512,
                          num_layers=2)
        m = LM(resolve(cfg, tp=1), _PAGED_RT)
        return LMBackend(
            name=name, model=m, params=m.init(jax.random.PRNGKey(seed)),
            tokenizer=tokz,
            rate_per_token=1.0 if name == "oracle" else 0.06,
            s_alloc=512, paged=paged)

    return CascadeEngine({"proxy": be("proxy", 1), "oracle": be("oracle", 2)},
                         OPS, n_classes=2, batch_size=batch_size)


# word counts straddle two buckets (32, 64); 50 makes the true fraction
# undershoot the padded one (ceil(50 * 0.25) = 13 < 16), so the op suffix
# decodes over positions holding LIVE document KV — the paged undo log's
# hard case
_PAGED_DOCS = {i: " ".join(f"w{i}x{j}" for j in range(n))
               for i, n in enumerate([20, 40, 28, 50, 12])}


def test_paged_engine_bitwise_parity_with_gather():
    """impl='pallas_interpret': the paged stage step (extend scatters the
    chunk in place, op suffix decodes over the arena behind the KV-window
    undo log) produces BITWISE identical preds/confs/per-doc $ to the
    PR-1 gather/scatter step — including an op-switch decode-only stage
    whose true fraction undershoots the cached padded fraction."""
    thr = {0: 2.0, 1: 2.0}       # impossible: every doc walks every stage
    ladder = Cascade([
        Task(TaskConfig("proxy", "sur_1", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),   # decode-only
        Task(TaskConfig("proxy", "o_orig", 0.5), thr),    # re-entry extend
    ])
    results = {}
    for paged in (False, True):
        eng = _mk_paged_engine(paged)
        assert eng.backends["proxy"].uses_paged_kv() == paged
        results[paged] = eng.run(ladder, _PAGED_DOCS)
    gather, paged = results[False], results[True]
    assert gather.pred == paged.pred
    assert gather.conf == paged.conf           # bitwise (python floats)
    assert gather.doc_cost == paged.doc_cost
    assert gather.cost == paged.cost
    assert gather.stats.batches == paged.stats.batches


def test_paged_op_suffix_leaves_arena_bitwise_pristine():
    """A decode-only op launch must not perturb the cached document rows:
    the undo log restores every dirtied position, so a second identical
    launch sees a bitwise-identical arena (same confidences out)."""
    eng = _mk_paged_engine(True)
    be = eng.backends["proxy"]
    d0 = 0
    toks = {d0: np.asarray(be.tokenizer.encode(_PAGED_DOCS[d0]), np.int32)}
    blen = bucket_len(len(toks[d0]))
    op = np.asarray(be.tokenizer.encode(OPS["o_orig"]), np.int32)
    be.run_stage([d0], toks, blen, 0.25, op, 2)       # prefill + op
    bucket_arena = be._arenas[blen]
    before = [np.asarray(l).copy()
              for l in jax.tree.leaves(bucket_arena.states)]
    _, c1, *_ = be.run_stage([d0], toks, blen, 0.25, op, 2)  # decode-only
    after = [np.asarray(l) for l in jax.tree.leaves(bucket_arena.states)]
    slot = be._doc_slot[d0][1]
    for b, a in zip(before, after):
        ax = 1 if b.ndim == 5 else 0          # scan-stacked vs tail leaves
        np.testing.assert_array_equal(np.take(b, [slot], ax),
                                      np.take(a, [slot], ax))
    _, c2, *_ = be.run_stage([d0], toks, blen, 0.25, op, 2)
    np.testing.assert_array_equal(c1, c2)


@pytest.mark.parametrize("arch,over,one_pass,doc_ids", [
    ("llama3_2_1b", {"num_layers": 2}, True, (0, 1, 3)),   # full attention
    ("gemma3_27b", {"num_layers": 6, "sliding_window": 8}, False, (3,)),
])
def test_one_pass_op_suffix_serves_as_the_loop(monkeypatch, arch, over,
                                               one_pass, doc_ids):
    """The one-pass operation suffix engages exactly for models whose
    layers are all full attention, and serves the answers of the
    per-token decode loop: same predictions and $, confidences to
    rounding.  A windowed model keeps the loop and still serves."""
    thr = {0: 2.0, 1: 2.0}       # impossible: every doc walks every stage
    ladder = Cascade([
        Task(TaskConfig("proxy", "sur_1", 0.25), thr),
        Task(TaskConfig("proxy", "o_orig", 0.25), thr),   # decode-only
        Task(TaskConfig("oracle", "o_orig", 1.0), thr),
    ])
    cfg = get_reduced(arch, dtype="float32", vocab_size=512, **over)
    m = LM(resolve(cfg, tp=1), CPU_TEST)
    tokz = HashWordTokenizer(vocab_size=512)

    def engine():
        return CascadeEngine({name: LMBackend(
            name=name, model=m, params=m.init(jax.random.PRNGKey(seed)),
            tokenizer=tokz, rate_per_token=1.0 if name == "oracle" else 0.06,
            s_alloc=512) for name, seed in (("proxy", 1), ("oracle", 2))},
            OPS, n_classes=2, batch_size=4)

    docs = {d: _PAGED_DOCS[d] for d in doc_ids}
    eng = engine()
    assert eng.backends["proxy"].one_pass_op_suffix == one_pass
    served = eng.run(ladder, docs)
    assert sorted(served.pred) == sorted(docs)
    if not one_pass:
        return                   # served through the loop already
    monkeypatch.setattr(LMBackend, "one_pass_op_suffix",
                        property(lambda self: False))
    loop = engine().run(ladder, docs)
    assert served.pred == loop.pred
    assert served.doc_cost == loop.doc_cost
    for d in docs:
        np.testing.assert_allclose(served.conf[d], loop.conf[d],
                                   atol=1e-5, rtol=1e-5)


def test_paged_gather_bytes_accounting():
    """The copy-traffic model behind the benchmark's paged section: the
    gather step moves whole [B, s_alloc] rows per launch, the paged step
    only the op-suffix undo log."""
    eng = _mk_paged_engine(True)
    be = eng.backends["proxy"]
    g = be.gather_bytes_per_launch(64, 4)
    assert g == 4 * be.slot_nbytes(64)
    p = be.paged_copy_bytes_per_launch(64, 4, 6)
    s_alloc = be._s_alloc_for(64)
    assert p == 2 * 4 * 6 * (be.slot_nbytes(64) // s_alloc)
    assert p < g // 8                          # undo log is tiny vs rows


# ---------------------------------------------------------------------------
# Hybrid (Mamba-2 + attention) models on the paged plane
# ---------------------------------------------------------------------------

def _hybrid_backend(name="oracle", seed=2, paged=None, tokz=None, rt=None,
                    arch="granite_4_0_h_micro", **over):
    tokz = tokz or HashWordTokenizer(vocab_size=512)
    if arch == "granite_4_0_h_micro":
        # one Mamba-2 and one attention layer: both kinds of serve state
        over = {"block_pattern": (MAMBA2, ATTN_FULL), "num_layers": 2,
                **over}
    cfg = get_reduced(arch, dtype="float32", vocab_size=512, **over)
    m = LM(resolve(cfg, tp=1), rt or _PAGED_RT)
    return LMBackend(name=name, model=m,
                     params=m.init(jax.random.PRNGKey(seed)), tokenizer=tokz,
                     rate_per_token=1.0 if name == "oracle" else 0.06,
                     s_alloc=512, paged=paged)


def _hybrid_engine(paged=None):
    tokz = HashWordTokenizer(vocab_size=512)
    return CascadeEngine(
        {n: _hybrid_backend(n, s, paged, tokz)
         for n, s in (("proxy", 1), ("oracle", 2))},
        OPS, n_classes=2, batch_size=4)


_WHOLE_DOCS = Cascade([
    Task(TaskConfig("proxy", "sur_1", 1.0), {0: 2.0, 1: 2.0}),
    Task(TaskConfig("oracle", "o_orig", 1.0), {0: 2.0, 1: 2.0}),
])


def test_hybrid_serves_on_the_paged_plane_in_one_op_pass(monkeypatch):
    """A Mamba-2 hybrid takes the paged plane and the one-pass operation
    suffix: no decode step runs, and a launch copies only the KV undo
    log (the attention layer's KV at op_len positions), its recurrent
    state counted apart as state traffic: written by a document's first
    extend and read by the op pass, or only read by a decode-only
    launch."""
    eng = _hybrid_engine()
    be = eng.backends["oracle"]
    assert be.uses_paged_kv() and be.one_pass_op_suffix

    def no_decode(*a, **k):
        raise AssertionError("a decode step ran")
    monkeypatch.setattr(LM, "decode_step", no_decode)
    res = eng.run(_WHOLE_DOCS, _PAGED_DOCS)
    assert sorted(res.pred) == sorted(_PAGED_DOCS)
    row = be.row_nbytes(64)
    assert row["kv"] and row["recurrent"]
    assert row["kv"] + row["recurrent"] == be.slot_nbytes(64)
    assert be.paged_copy_bytes_per_launch(64, 2, 6) \
        == 2 * 2 * 6 * (row["kv"] // be._s_alloc_for(64))
    recs = [r for r in eng.telemetry.launches.items() if r.model == "oracle"]
    assert recs and all(
        r.state_bytes == r.batch * (1 if r.decode_only else 2)
        * be.row_nbytes(r.bucket)["recurrent"] for r in recs)
    reg = eng.telemetry.registry               # the arena's bytes by kind
    kinds = {k: reg.gauge("serve_arena_kind_bytes", backend="oracle",
                          kind=k).value for k in ("kv", "recurrent")}
    assert kinds["kv"] > 0 and kinds["recurrent"] > 0
    assert kinds["kv"] + kinds["recurrent"] == be.arena_nbytes()


def test_hybrid_paged_engine_bitwise_parity_with_gather():
    """Paged (state taken and put by slot, KV in place) and gather (row
    copies) planes serve a hybrid bitwise alike."""
    runs = {p: _hybrid_engine(p).run(_WHOLE_DOCS, _PAGED_DOCS)
            for p in (False, True)}
    assert runs[False].pred == runs[True].pred
    assert runs[False].conf == runs[True].conf
    assert runs[False].doc_cost == runs[True].doc_cost


def test_hybrid_op_pass_leaves_arena_bitwise_unchanged():
    """The op pass writes its KV behind the undo log and never writes a
    recurrent state: a decode-only launch leaves every arena leaf
    bitwise as the document pass left it, so it answers again alike."""
    be = _hybrid_backend()
    toks = {d: np.asarray(be.tokenizer.encode(t), np.int32)
            for d, t in _PAGED_DOCS.items()}
    op = np.asarray(be.tokenizer.encode(OPS["o_orig"]), np.int32)
    ids = [1, 3]                                   # both in bucket 64
    _, c1, *_ = be.run_stage(ids, toks, 64, 1.0, op, 2)
    before = [np.asarray(l).copy() for l in jax.tree.leaves(be._arenas[64]
                                                            .states)]
    _, c2, new_t, _ = be.run_stage(ids, toks, 64, 1.0, op, 2)
    assert new_t == 2 * len(op)                    # decode-only
    for b, a in zip(before, jax.tree.leaves(be._arenas[64].states)):
        np.testing.assert_array_equal(b, np.asarray(a))
    np.testing.assert_array_equal(c1, c2)


def test_hybrid_fraction_extension_equals_one_shot():
    """Fraction 0.5 then 1.0 on the same hybrid resumes the recurrent
    state exactly: the answer equals one prefill of the whole document
    (1e-5: float32 reassociation between one extend and two).  The
    document fills its bucket, so each stage reads every true token it
    caches."""
    be = _hybrid_backend()
    d = 9
    toks = {d: np.asarray(be.tokenizer.encode(
        " ".join(f"f{j}" for j in range(64))), np.int32)}
    op = np.asarray(be.tokenizer.encode("test op"), np.int32)
    be.run_stage([d], toks, 64, 0.5, op, 2)
    _, c_ext, new_t, cached_t = be.run_stage([d], toks, 64, 1.0, op, 2)
    assert cached_t == 32
    be.reset()
    _, c_one, *_ = be.run_stage([d], toks, 64, 1.0, op, 2)
    np.testing.assert_allclose(c_ext, c_one, atol=1e-5)


def test_hybrid_refuses_a_fraction_short_of_its_cached_prefix():
    """A recurrent state holds one prefix: a stage that reads fewer true
    tokens than it caches (ceil(50 * 0.5) = 25 of the 32 padded) is
    refused, not answered from the wrong state."""
    be = _hybrid_backend()
    toks = {3: np.asarray(be.tokenizer.encode(_PAGED_DOCS[3]), np.int32)}
    op = np.asarray(be.tokenizer.encode("op"), np.int32)
    with pytest.raises(ValueError, match="recurrent state"):
        be.run_stage([3], toks, 64, 0.5, op, 2)


@pytest.mark.parametrize("arch,paged", [
    ("granite_4_0_h_micro", True),      # Mamba-2, paged plane
    ("xlstm_350m", False),              # mLSTM + sLSTM, gather plane
    ("recurrentgemma_2b", False),       # RG-LRU (+ local attention)
])
def test_recycled_slot_starts_from_the_initial_state(arch, paged):
    """A slot freed by one document and handed to the next starts
    every recurrent layer from its initial state: the next document
    answers as on a fresh arena, whatever the last one left in the
    row."""
    rt = _PAGED_RT if paged else CPU_TEST
    be = _hybrid_backend(paged=paged, rt=rt, arch=arch)
    toks = {d: np.asarray(be.tokenizer.encode(t), np.int32)
            for d, t in _PAGED_DOCS.items()}
    op = np.asarray(be.tokenizer.encode(OPS["o_orig"]), np.int32)
    be.run_stage([1], toks, 64, 1.0, op, 2)
    slot = be._doc_slot[1]
    be.release(1)
    _, recycled, *_ = be.run_stage([3], toks, 64, 1.0, op, 2)
    assert be._doc_slot[3] == slot
    be.reset()
    _, fresh, *_ = be.run_stage([3], toks, 64, 1.0, op, 2)
    np.testing.assert_allclose(recycled, fresh, atol=1e-6)
