"""The output check's control, judged by the check itself: the reference
with fp8 weights (one scale per output channel) put in the program's
place, its answers compared by ``check.compare`` at the cell's limits.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11,12,13

For each seed it makes the cell's weights and the documents of a window
of ``--seconds`` (``Traffic.window_docs``, every one of which a sound run
resolves), draws the check's sample from them as a run does, answers
each sampled document with the control, and prints one JSON line with
``correct`` and the checks, each number beside its limit.  A control the
check catches reads ``correct: false``.  The benchmark's own runs never
run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Callable, Dict

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_run(cell, seed: int, seconds: float,
                log: Callable = lambda *a: None) -> Dict[str, Any]:
    import numpy as np

    import check as CK
    import harness
    import traffic as TR
    mix, models = cell.mix, cell.config["models"]
    n_classes = int(mix["classes"])
    traffic = TR.Traffic(mix, seed)
    docs = [traffic.doc(k) for k in range(traffic.window_docs(seconds))]
    sample = traffic.sample(docs)
    ops = traffic.operations()
    params = {role: cell.arch[role].make_params(models[role], seed, i)
              for i, role in enumerate(("proxy", "oracle"))}
    answered = []
    for d in sample:
        role = CK.stage_role(mix, d.exit_stage)
        _, toks = CK.stage_prompt(mix, ops, models[role]["vocab_size"],
                                  d.text, d.exit_stage)
        z = cell.arch[role].class_logits(params[role], models[role], toks,
                                         n_classes, control=True)
        p = np.exp(z - z.max())
        p /= p.sum()
        answered.append(harness.Served(
            d, 0.0, 0.0, status="resolved", pred=int(p.argmax()),
            conf=float(p.max()), exit_stage=d.exit_stage))
    checks = CK.compare(cell, params, answered, answered, ops, log)
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "checks": checks}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    harness.device_info(cell.chips, require_tpu=True)
    harness.enable_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_run(cell, seed, args.seconds,
                          log=lambda *a: print(*a, file=sys.stderr))
        print(json.dumps({"workload": cell.name, "seed": seed, **out}),
              flush=True)


if __name__ == "__main__":
    main()
