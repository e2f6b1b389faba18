"""Host milliseconds per launch in the window: the scheduler's pick and
the dispatch of the jitted step, both measured directly by the server's
launch timeline (control plane, program spans).  The timeline's ``host``
segment is left out: it is a residual that, with two launches in flight,
also holds the time a launch waits behind the other."""


def read(run):
    recs = run.launches
    if not recs:
        return None
    return 1e3 * sum(r.sched_s + r.dispatch_s for r in recs) / len(recs)
