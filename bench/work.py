"""The work a cascade stage requires, counted from shapes and true lengths.

What is counted is what any implementation has to do to answer the
stage: every true document token that is new to the model, and every
operation token, goes once through every layer; each of those tokens
attends once to the keys before it; the head is read at the last
position over the class rows only.  Bucket padding, launch-width
padding, re-reading the weights once per decoded token and a prefix
computed twice are not counted, so a share of a peak built on these
counts reads the same work whatever implements it, and cannot pass 100%
by a change of implementation.

Each architecture counts its own stage (``stage_work`` of
``arch/<model_type>.py``, from the published config keys); this module
keeps what no architecture changes.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Mapping, Sequence, Tuple

BF16_BYTES = 2


def keys_attended(start: int, stop: int) -> int:
    """Sum over query positions p in [start, stop) of the p + 1 keys each
    sees under a causal mask."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def true_prefix(doc_len: int, fraction: float) -> int:
    """True tokens of a document that a stage at ``fraction`` reads."""
    return max(int(math.ceil(doc_len * fraction)), 1)


def document_work(models: Mapping[str, Tuple[Mapping[str, Any], Any]],
                  stages: Sequence[Tuple[str, str, float]],
                  visited: Iterable[int], doc_len: int,
                  op_tokens: Mapping[str, int], n_classes: int
                  ) -> Dict[str, Dict[str, int]]:
    """Work of one document's visits to ``stages[i]`` for i in ``visited``
    (in order), summed per model.  ``models`` maps each model to its
    config entry and its ``arch/<model_type>.py`` module, whose
    ``stage_work`` counts the visit.  A later stage on the same model
    reuses the prefix an earlier one read."""
    seen: Dict[str, int] = {}
    out: Dict[str, Dict[str, int]] = {}
    for i in visited:
        model, op, fraction = stages[i]
        n = true_prefix(doc_len, fraction)
        cached = min(seen.get(model, 0), n)
        m, arch = models[model]
        w = arch.stage_work(m, cached, n, op_tokens[op], n_classes)
        seen[model] = max(seen.get(model, 0), n)
        acc = out.setdefault(model, {})
        for k, v in w.items():
            acc[k] = acc.get(k, 0) + v
    return out
