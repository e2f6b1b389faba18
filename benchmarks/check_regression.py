#!/usr/bin/env python
"""Benchmark-regression gate for CI.

Diffs a fresh ``benchmarks/serve_engine.py --smoke`` summary against the
``"smoke"`` section committed in ``BENCH_serve_engine.json``, with an
EXPLICIT per-metric tolerance table.  Every gated metric is deterministic
for a given source tree (seeded corpora/params, blake2 word hashing,
forced-impossible thresholds, tick-based interactive replay), so the
tolerances are tight: structural counts (tokens, launches, copy bytes)
must match exactly, float aggregates ($, occupancy) within 1e-6
relative.  Timing metrics (docs/s, latency) are intentionally NOT gated.
The chaos (fault-injection) section is gated on its boolean invariants
only — all docs terminal, exact accounting, journal recovery — since its
counters vary with ``--chaos-seed``; the fault-free metrics above must
stay byte-identical whether or not injection ran.  The capacity section
(prefix sharing + bf16 arenas) pins its own per-arm dtypes, so its gates
hold on the ``--kv-dtype=bf16`` smoke leg too — the one committed
baseline serves both legs.

    python benchmarks/serve_engine.py --smoke          # writes BENCH_smoke.json
    python benchmarks/check_regression.py BENCH_smoke.json \
        --baseline BENCH_serve_engine.json

Exit status 0 = within tolerance; 1 = drift (every violation listed).
An intentional change to the serving economics (token accounting, packing
policy, copy-traffic model) regenerates the baseline by re-running the
full benchmark: ``python benchmarks/serve_engine.py``.
"""
from __future__ import annotations

import argparse
import json
import sys

# metric path inside the "smoke" section -> (kind, tolerance)
#   exact  values must be equal (ints, bools, structural byte counts)
#   rel    |fresh - base| <= tol * max(|base|, 1e-12)   (floats, lists of
#          floats elementwise; length mismatch is a violation)
TOLERANCES = {
    # static arena engine: token/$ accounting and launch schedule
    "static.new_tokens":                      ("exact", 0),
    "static.cached_tokens":                   ("exact", 0),
    "static.launches":                        ("exact", 0),
    "static.cost":                            ("rel", 1e-6),
    "static.cache_hit_rate":                  ("rel", 1e-6),
    # multi-tenant interactive replay: cross-query packing
    "multi_tenant.shared_launches":           ("exact", 0),
    "multi_tenant.isolated_launches":         ("exact", 0),
    "multi_tenant.occupancy":                 ("rel", 1e-6),
    "multi_tenant.isolated_occupancy":        ("rel", 1e-6),
    "multi_tenant.per_query_cost":            ("rel", 1e-6),
    # paged data plane: structural copy traffic
    "paged.gather_copy_bytes_per_launch":     ("exact", 0),
    "paged.paged_arena_copy_bytes_per_launch": ("exact", 0),
    "paged.paged_undo_log_bytes_per_launch":  ("exact", 0),
    # default doc-before-op plane: prefix-sharing counters structurally 0
    # (the capacity section exercises the nonzero paths)
    "static.prefix_hits":                     ("exact", 0),
    "static.cow_copies":                      ("exact", 0),
    "static.re_prefill_tokens":               ("exact", 0),
    # capacity: prefix sharing + bf16 arenas under a fixed byte budget.
    # The arms pin their own dtypes/planes, so every number here is
    # byte-identical whatever --kv-dtype the smoke leg ran under.
    # (static.arena_bytes_peak is intentionally NOT gated: it halves on
    # the bf16 leg; the per-arm peaks below pin the byte accounting.)
    "capacity.byte_budget":                   ("exact", 0),
    "capacity.no_pressure.f32_private.arena_bytes_peak": ("exact", 0),
    "capacity.no_pressure.f32_prefix.arena_bytes_peak": ("exact", 0),
    "capacity.no_pressure.bf16_prefix.arena_bytes_peak": ("exact", 0),
    "capacity.no_pressure.f32_prefix.prefix_hits": ("exact", 0),
    "capacity.no_pressure.f32_prefix.cow_copies": ("exact", 0),
    "capacity.no_pressure.f32_prefix.cost":   ("rel", 1e-6),
    "capacity.overload.f32_private.evictions": ("exact", 0),
    "capacity.overload.f32_private.re_prefill_tokens": ("exact", 0),
    "capacity.overload.bf16_prefix.evictions": ("exact", 0),
    "capacity.overload.bf16_prefix.re_prefill_tokens": ("exact", 0),
    # telemetry trace probe: structural span/event/launch counts from the
    # FIXED-seed chaos workload (a pure function of the source tree —
    # zero backoff, logical arrivals — so they gate exactly; timings in
    # the embedded snapshot are intentionally NOT gated)
    "telemetry.trace_probe.spans":             ("exact", 0),
    "telemetry.trace_probe.events_total":      ("exact", 0),
    "telemetry.trace_probe.launch_records":    ("exact", 0),
    "telemetry.trace_probe.failed_launch_records": ("exact", 0),
    "telemetry.trace_probe.metric_series":     ("exact", 0),
}

# invariants the FRESH summary must satisfy regardless of the baseline
REQUIRED_TRUE = (
    "multi_tenant.pred_match",
    "multi_tenant.doc_cost_parity_exact",
    "paged.parity.pred_match",
    "paged.parity.conf_bitwise",
    "paged.parity.doc_cost_parity_exact",
    # capacity (prefix sharing + bf16 KV compression): the op-token memo
    # and the compressed arena must leave the $-ledger exactly unchanged
    # (same-op ladder), bf16 preds/confs must sit within the gated
    # tolerance of f32, and under the fixed byte budget the bf16 arm must
    # resolve the same overload with strictly fewer evictions and >= 1.8x
    # fewer re-prefilled tokens than the f32 private baseline
    "capacity.parity.doc_cost_parity_exact",
    "capacity.parity.bf16_within_tolerance",
    "capacity.overload.fewer_evictions_bf16",
    "capacity.overload.reprefill_reduction_ge_1_8",
    # chaos (fault injection): every submitted document reaches a terminal
    # state, per-query/per-document $ replay the billing ledger exactly,
    # and a mid-flight crash warm-restarts from the write-ahead journal
    # (counts — retries, quarantines, trips — vary with --chaos-seed and
    # are intentionally NOT gated)
    "chaos.all_docs_terminal",
    "chaos.accounting_exact",
    "chaos.deadline_timed_out",
    "chaos.arena_loss_injected",
    "chaos.recovery_all_terminal",
    "chaos.recovery_restored_exact",
    "chaos.recovery_accounting_exact",
    # telemetry (PR 8): the default-on counters level must be bitwise
    # invisible to the fault-free data plane (preds/confs/per-doc $ and
    # arena device state equal a level="off" run exactly); the trace
    # probe's spans must be well-formed under injected faults, nothing
    # dropped from the bounded rings at gate scale, and every launch's
    # sched/host/dispatch/sync segments must sum to its wall time
    "telemetry.counters_bitwise_inert",
    "telemetry.trace_probe.spans_well_formed",
    "telemetry.trace_probe.no_dropped_events",
    "telemetry.trace_probe.segments_sum_ok",
    # overlap (ahead-of-time dispatch, ROADMAP item 2): the K-deep
    # dispatch window must actually be reached (max_inflight >= 2), the
    # overlap metrics (overlap_hidden_frac, mean_launch_gap_ms) must be
    # present in the snapshot timeline, and the fault-free plane must be
    # BITWISE identical to inflight=1 — preds, confs, per-document $,
    # and every arena device leaf (gap/hidden-fraction values are
    # wall-clock and intentionally NOT gated)
    "overlap.max_inflight_ge_2",
    "overlap.metrics_present",
    "overlap.parity.pred_match",
    "overlap.parity.conf_bitwise",
    "overlap.parity.doc_cost_parity_exact",
    "overlap.parity.arena_leaves_bitwise",
)


def _get(tree, path: str):
    for part in path.split("."):
        tree = tree[part]
    return tree


def _rel_ok(fresh: float, base: float, tol: float) -> bool:
    return abs(float(fresh) - float(base)) <= tol * max(abs(float(base)),
                                                        1e-12)


def section_diff(fresh: dict, base: dict) -> list:
    """Top-level section drift between the fresh summary and the
    baseline, reported in BOTH directions.  A section present in the
    baseline but absent from the fresh run means the benchmark silently
    stopped producing it (the per-metric loop would only say 'missing
    from fresh' for gated paths); a fresh-only section means the
    baseline predates it and must be regenerated."""
    violations = []
    missing = sorted(set(base) - set(fresh))
    extra = sorted(set(fresh) - set(base))
    if missing:
        violations.append(
            f"sections missing from fresh summary: {missing} "
            f"(baseline has {sorted(base)})")
    if extra:
        violations.append(
            f"sections missing from baseline: {extra} "
            f"(regenerate BENCH_serve_engine.json)")
    return violations


def compare(fresh: dict, base: dict) -> list:
    """Return the list of violations (empty = gate passes)."""
    violations = section_diff(fresh, base)
    for path, (kind, tol) in TOLERANCES.items():
        try:
            f = _get(fresh, path)
        except (KeyError, TypeError):
            violations.append(f"{path}: missing from fresh summary")
            continue
        try:
            b = _get(base, path)
        except (KeyError, TypeError):
            violations.append(f"{path}: missing from baseline "
                              f"(regenerate BENCH_serve_engine.json)")
            continue
        if isinstance(b, list) or isinstance(f, list):
            if not isinstance(f, list) or not isinstance(b, list) \
                    or len(f) != len(b):
                violations.append(f"{path}: shape mismatch {f!r} vs {b!r}")
                continue
            pairs = list(zip(f, b))
        else:
            pairs = [(f, b)]
        for i, (fv, bv) in enumerate(pairs):
            tag = f"{path}[{i}]" if len(pairs) > 1 else path
            if kind == "exact":
                if fv != bv:
                    violations.append(
                        f"{tag}: {fv!r} != baseline {bv!r} (exact)")
            else:
                if not _rel_ok(fv, bv, tol):
                    violations.append(
                        f"{tag}: {fv!r} vs baseline {bv!r} "
                        f"(rel tol {tol:g})")
    for path in REQUIRED_TRUE:
        try:
            if _get(fresh, path) is not True:
                violations.append(f"{path}: must be true, got "
                                  f"{_get(fresh, path)!r}")
        except (KeyError, TypeError):
            violations.append(f"{path}: missing from fresh summary")
    return violations


def _load_section(path: str, which: str) -> dict:
    """Load the gated ``"smoke"`` section of ``path`` or exit 2 with a
    diagnostic naming the file, the missing piece, and the keys that ARE
    there — a truncated/renamed summary must not surface as a KeyError."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        print(f"regression gate: {which} file not found: {path}")
        raise SystemExit(2)
    except json.JSONDecodeError as e:
        print(f"regression gate: {which} {path} is not valid JSON: {e}")
        raise SystemExit(2)
    if not isinstance(doc, dict) or "smoke" not in doc:
        keys = sorted(doc) if isinstance(doc, dict) else type(doc).__name__
        fix = ("re-run benchmarks/serve_engine.py --smoke"
               if which == "fresh summary"
               else "regenerate it with benchmarks/serve_engine.py")
        print(f"regression gate: {which} {path} has no 'smoke' section "
              f"(top-level keys: {keys}); {fix}")
        raise SystemExit(2)
    smoke = doc["smoke"]
    if not isinstance(smoke, dict):
        print(f"regression gate: {which} {path} 'smoke' section is "
              f"{type(smoke).__name__}, expected an object")
        raise SystemExit(2)
    return smoke


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("summary", help="fresh --smoke summary JSON")
    ap.add_argument("--baseline", default="BENCH_serve_engine.json",
                    help="committed benchmark JSON holding the baseline "
                         "'smoke' section")
    args = ap.parse_args()
    fresh = _load_section(args.summary, "fresh summary")
    base = _load_section(args.baseline, "baseline")
    violations = compare(fresh, base)
    if violations:
        print(f"REGRESSION GATE FAILED ({len(violations)} violation(s) "
              f"vs {args.baseline}):")
        for v in violations:
            print(f"  - {v}")
        return 1
    n = len(TOLERANCES) + len(REQUIRED_TRUE)
    print(f"regression gate OK: {n} gated metrics within tolerance "
          f"of {args.baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
