"""Documents per launch in the window (scheduler, program counter)."""


def read(run):
    recs = run.launches
    return sum(r.batch for r in recs) / len(recs) if recs else None
