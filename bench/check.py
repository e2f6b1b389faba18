"""The comparison that decides ``correct``.

For each sampled document the reference (``class_logits`` of the stage
model's ``arch/<model_type>.py``, float32) reads the class logits after
the prompt its exit stage served — the stage's fraction of the document
followed by the stage's operation — through that stage's model, with
the same weights.  The served answer is the class ``pred`` and its
confidence ``conf`` (softmax over the class logits).  The number
compared is the widest, over the sample, of

    max( lp[best] - lp[pred],  |log conf - lp[pred]| )

where ``lp`` is the reference's log-softmax over the classes: how far the
served class lies below the reference's best, and how far the served
confidence is from the reference's for that class, both in nats.  The
routing is checked exactly: every document must resolve at the stage the
traffic routed it to.

Limits come from readings of the program and of the control (fp8
weights, ``control.py``) on the chip; ``PERF.md`` gives them.  They live in the traffic
mix file (``limits``), one per cell's mix.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, Mapping, Sequence

import numpy as np

import reference as REF
import work as WK


def stage_prompt(mix: Mapping[str, Any], ops: Mapping[str, str],
                 vocab: int, text: str, stage: int):
    """(model role, token ids) of the prompt ``stage`` serves for a doc."""
    stages = [tuple(s) for s in mix["stages"]] + [
        ("oracle", mix["oracle_op"], 1.0)]
    model, op, fraction = stages[stage]
    doc = REF.tokenize(text, vocab)
    n = WK.true_prefix(len(doc), float(fraction))
    return model, doc[:n] + REF.tokenize(ops[op], vocab)


def answer_gap(ref_logits: np.ndarray, pred: int, conf: float) -> float:
    z = ref_logits - ref_logits.max()
    lp = z - math.log(np.exp(z).sum())
    if not (0 < conf <= 1) or not math.isfinite(conf):
        return math.inf
    return float(max(lp.max() - lp[pred], abs(math.log(conf) - lp[pred])))


def compare(cell, params: Mapping[str, Any], resolved: Sequence[Any],
            sample: Sequence[Any], ops: Mapping[str, str],
            log: Callable = print) -> Dict[str, Dict]:
    """Checks of the served documents (``harness.Served``): the routing of
    every resolved one, the answers of the sample."""
    mix, models = cell.mix, cell.config["models"]
    n_classes = int(mix["classes"])
    mismatched = sum(s.exit_stage != s.doc.exit_stage for s in resolved)
    t0 = time.perf_counter()
    worst = 0.0
    for s in sample:
        vocab = models[stage_role(mix, s.doc.exit_stage)]["vocab_size"]
        role, toks = stage_prompt(mix, ops, vocab, s.doc.text,
                                  s.doc.exit_stage)
        logits = cell.arch[role].class_logits(params[role], models[role],
                                              toks, n_classes)
        gap = answer_gap(logits, s.pred, s.conf)
        worst = max(worst, gap)
        log(f"  doc {s.doc.index} ({s.doc.n_tokens} tokens) stage "
            f"{s.doc.exit_stage} on {role}: served pred {s.pred} conf "
            f"{s.conf:.6f}; reference logits "
            f"{np.array2string(logits, precision=4)}; gap {gap:.3e}")
    log(f"bench: reference over {len(sample)} documents in "
        f"{time.perf_counter() - t0:.1f}s")
    limits = mix["limits"]
    return {"class_logprob_gap": {"value": worst,
                                  "limit": float(limits["class_logprob_gap"])},
            "exit_stage_mismatch": {"value": mismatched, "limit": 0}}


def stage_role(mix: Mapping[str, Any], stage: int) -> str:
    return (mix["stages"][stage][0] if stage < len(mix["stages"])
            else "oracle")
