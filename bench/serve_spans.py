"""Step programs in a profiler trace matched to the launches that ran
them, and the op-suffix phase of each matched program.

The server wraps each launch's jitted step call in a ``serve.dispatch``
span and its completion wait in a ``serve.sync`` span
(``repro.serving.telemetry.Telemetry.span``).  The same spans are the
launch timeline's segments, so each ``LaunchRecord`` holds its
``serve.dispatch`` start (``ts_enqueue``) and its ``serve.sync`` end
(``ts_ready``).

Matching.  The device runs a process's programs first in, first out, so
consecutive launches ran consecutive step programs: launch ``j`` of a
run of launches ran program ``j + c`` for one offset ``c``.  A program
starts after its launch's ``serve.dispatch`` began and ends before its
``serve.sync`` ended.  The offset kept is the one under which the pairs
that meet both bounds most outnumber those that break one, and only the
pairs that meet them are matched.  A program from before the trace
opened, a launch whose program left the trace, a dispatch that raised
and two launches in flight shift nothing.

Op suffix.  A ``paged_step`` program extends the launch's new tokens
through the paged flash kernel, once per layer, then the operation's
tokens, as one chunk, through the same kernel, once per layer.  Its
op-suffix phase runs from the start of the op chunk's first kernel (the
``L + 1``-th kernel of a launch with new tokens, the first of a
decode-only launch, ``L`` the model's paged flash kernels in one pass)
to the program's end: the op chunk, the undo log's restore and the class
head.  A program with no kernel past its ``L`` extend kernels (the
operation extended with the document) has no op suffix.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

TOLERANCE_S = 5e-4        # clock error allowed at the match's two bounds

Interval = Tuple[float, float]


@dataclass(frozen=True)
class LaunchAnchor:
    """One launch as its spans bound its program: the ``serve.dispatch``
    start (None where it is not known) and the ``serve.sync`` end."""
    index: int
    dispatch_start: Optional[float]
    sync_end: float


def _fits(prog: Interval, a: LaunchAnchor) -> bool:
    return ((a.dispatch_start is None
             or prog[0] >= a.dispatch_start - TOLERANCE_S)
            and prog[1] <= a.sync_end + TOLERANCE_S)


def match_programs(programs: Sequence[Interval],
                   launches: Sequence[LaunchAnchor]
                   ) -> List[Tuple[LaunchAnchor, int]]:
    """(launch, position in ``programs``) for each launch matched to a
    step program; ``programs`` in the order they ran, ``launches`` in the
    order they were dispatched, on one clock."""
    best: List[Tuple[LaunchAnchor, int]] = []
    best_score = None
    for c in range(-len(launches) + 1, len(programs)):
        pairs = [(a, j + c) for j, a in enumerate(launches)
                 if 0 <= j + c < len(programs)]
        fits = [(a, pos) for a, pos in pairs if _fits(programs[pos], a)]
        score = (2 * len(fits) - len(pairs), len(fits))
        if best_score is None or score > best_score:
            best, best_score = fits, score
    return best


def op_suffix_seconds(programs: Sequence[Interval],
                      kernels: Sequence[Interval],
                      matched: Sequence[Tuple[int, int, bool]]
                      ) -> Tuple[float, float, int]:
    """(op-suffix seconds, program seconds, programs skipped) over the
    matched programs, each given as (position in ``programs``, the
    model's paged flash kernels in one pass, whether its launch had new
    tokens); ``kernels`` sorted by start.  A program with fewer kernels
    than the rule reads (under ``L`` with new tokens, none decode-only)
    is skipped and counted: the trace lost some of its kernels."""
    starts = [k[0] for k in kernels]
    suffix = total = 0.0
    skipped = 0
    for pos, layers, new_tokens in matched:
        s, e = programs[pos]
        inside = [k for k in kernels[bisect.bisect_left(starts, s):
                                     bisect.bisect_right(starts, e)]
                  if k[1] <= e]
        first = layers if new_tokens else 0     # the op chunk's first kernel
        if len(inside) < max(first, 1):
            skipped += 1
            continue
        if len(inside) > first:
            suffix += e - inside[first][0]
        total += e - s
    return suffix, total, skipped
