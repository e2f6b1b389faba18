"""Serve a full-width task cascade on one TPU chip, and check what comes out.

    python3 chip_smoke.py [--docs-per-tenant 16] [--seed 0]

One process, the only one touching JAX.  It builds the server through the
serving entry point (``repro.launch.serve.build_engine``): proxy
llama3.2-1b and oracle qwen3-1.7b at their published widths in bf16,
random weights from ``--seed``, on the paged Pallas plane with two launches
in flight (donated arenas, overlapped dispatch).  Two tenants stream
documents of the 256-token bucket into it, and every document must
resolve with no launch failure, retry, quarantine or breaker trip.  A few
documents are then replayed stage by stage through the plain XLA attention
path with the same weights, and their confidences must agree within
``CONF_TOL``.  The last line of output is one JSON object naming the
device.  Off a TPU the script exits non-zero before printing any result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# Two bf16 forward passes that differ only in how attention is reduced
# (Pallas flash over the arena vs XLA's blocked scan over a gathered copy)
# agree to a few bf16 ulps (2^-8 relative) per attention output; through
# the residual stream that reaches the class logits as an absolute gap of
# order 1e-2.  The served confidence is sigmoid(|l1 - l0|) of the two class
# logits, whose slope is at most 1/4, so agreement within 0.02 is what
# bf16 allows, and a wrong kernel (a wrong head, block or mask) moves
# confidences by far more.
CONF_TOL = 0.02
BUCKET = 256
REPLAYED = 4


def _device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--docs-per-tenant", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--rate", type=float, default=64.0,
                    help="Poisson arrivals per second, per tenant")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    dev = _device()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev['platform']!r}")

    import jax
    import numpy as np

    from repro.data.documents import generate_corpus
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import (build_engine, drive_server,
                                    poisson_arrivals, tenant_cascades,
                                    unresolved_docs, warm_arena)
    from repro.models.model import LM
    from repro.models.runtime import Runtime
    from repro.serving.engine import LMBackend
    from repro.serving.scheduler import bucket_len

    cache = enable_compile_cache()
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
          f"compile cache {cache}", flush=True)

    # ---- documents: one length bucket keeps the launch signatures few
    n_docs = 2 * args.docs_per_tenant
    docs, seed = {}, args.seed
    while len(docs) < n_docs:
        for d in generate_corpus(4 * n_docs, avg_lines=19, seed=seed):
            if (len(docs) < n_docs
                    and bucket_len(len(d.text.split())) == BUCKET):
                docs[len(docs)] = d.text
        seed += 1000

    # ---- the server, through the serving entry point
    t0 = time.perf_counter()
    # two launches in flight: donated arenas + overlapped dispatch
    server = build_engine(args.batch, None, retire_after=64, seed=args.seed,
                          inflight=2)
    for be in server.backends.values():
        # room for every document up front: arena capacity is part of the
        # compiled shape, so a growth mid-stream would compile again
        be.init_slots = n_docs
    jax.block_until_ready([be.params for be in server.backends.values()])
    t_build = time.perf_counter() - t0
    for name, be in server.backends.items():
        r = be.model.rcfg
        assert be.model.rt.attn_impl == "pallas", name
        assert be.uses_paged_kv(), f"{name} is not on the paged plane"
        print(f"{name}: {r.base.name} layers {r.base.num_layers} d_model "
              f"{r.base.d_model} heads {r.padded_heads}/{r.padded_kv_heads} "
              f"head_dim {r.head_dim} vocab {r.padded_vocab} "
              f"{r.base.dtype}; {be.params_nbytes() / 2**30:.2f} GiB "
              f"weights; attn_impl {be.model.rt.attn_impl}, paged",
              flush=True)
    print(f"built in {t_build:.1f}s", flush=True)

    # ---- warm-up: every (stage signature, launch width) compiles here
    cascades = tenant_cascades(2)
    t0 = time.perf_counter()
    distinct = {tuple(t.config.key() for t in c.tasks): c for c in cascades}
    for cascade in distinct.values():
        warm_arena(server, cascade, docs, args.batch)
    t_warm = time.perf_counter() - t0
    print(f"warm-up (compiles every signature): {t_warm:.1f}s", flush=True)

    # ---- the served pass: two tenants streaming on one server
    server.reset()
    handles = [server.register(c) for c in cascades]
    ids = sorted(docs)
    streams = []
    for k, h in enumerate(handles):
        mine = {d: docs[d] for d in ids[k::2]}
        streams.append((h, mine, poisson_arrivals(sorted(mine), args.rate,
                                                  args.seed + k)))
    results, wall = drive_server(server, streams)

    unresolved = unresolved_docs(results)
    assert not unresolved, f"documents not RESOLVED: {unresolved}"
    agg = server.stats()
    snap = server.telemetry_snapshot()["server"]
    faults = {"failed_launches": snap["failed_launches"],
              "retries": agg.retries, "quarantines": agg.quarantines,
              "breaker_trips": agg.breaker_trips, "failures": agg.failures,
              "timeouts": agg.timeouts}
    assert not any(faults.values()), f"faults on the served path: {faults}"
    confs = [c for r in results.values() for c in r.conf.values()]
    assert len(confs) == n_docs and all(math.isfinite(c) for c in confs)
    exits = [s for r in results.values() for s in r.exit_stage.values()]
    print(f"served {len(confs)}/{n_docs} docs RESOLVED in {wall:.2f}s wall "
          f"({len(confs) / wall:.1f} docs/s at {args.rate}/s per tenant); "
          f"{agg.batches} launches, max in flight "
          f"{snap['max_inflight']}; exit stages "
          + ", ".join(f"{s}:{exits.count(s)}" for s in sorted(set(exits)))
          + f"; faults {faults}", flush=True)

    # ---- reference: same weights, plain XLA attention, gather plane
    refs = {}
    for name, be in server.backends.items():
        m = LM(be.model.rcfg, Runtime(attn_impl="xla"))
        refs[name] = LMBackend(name=name, model=m, params=be.params,
                               tokenizer=be.tokenizer, paged=False)
    t0 = time.perf_counter()
    worst, agree, n = 0.0, 0, 0
    for h, mine, _ in streams:
        res = results[h.query_id]
        for d in sorted(mine)[:REPLAYED // len(streams)]:
            key = h.query_id * 100_000 + d
            conf = pred = None
            for s in range(res.exit_stage[d] + 1):
                model, op_id, fraction, _ = h.stages[s]
                be = refs[model]
                toks = np.asarray(be.tokenizer.encode(mine[d]), np.int32)
                op = np.asarray(be.tokenizer.encode(server.operations[op_id]),
                                np.int32)
                p, c, _, _ = be.run_stage([key], {key: toks},
                                          bucket_len(len(toks)), fraction,
                                          op, server.n_classes)
                pred, conf = int(p[0]), float(c[0])
            gap = abs(conf - res.conf[d])
            worst = max(worst, gap)
            agree += pred == res.pred[d]
            n += 1
            print(f"  query {h.query_id} doc {d}: served conf "
                  f"{res.conf[d]:.6f} pred {res.pred[d]}, reference conf "
                  f"{conf:.6f} pred {pred}, |gap| {gap:.2e}", flush=True)
    print(f"reference agreement: max |conf gap| {worst:.3e} over {n} docs "
          f"(tolerance {CONF_TOL}); preds agree {agree}/{n}; "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    assert worst <= CONF_TOL, f"served vs reference conf gap {worst}"

    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
