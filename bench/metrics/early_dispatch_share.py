"""Share of the window's launches that a step dispatched early: after it
completed its oldest launch, inside the same ``CascadeServer.step()``, so
the device ran them while the client submitted and polled (control
plane, program counter: ``LaunchRecord.early``).  None where the records
carry no such field."""


def read(run):
    recs = run.launches
    if not recs or not all(hasattr(r, "early") for r in recs):
        return None
    return 100.0 * sum(bool(r.early) for r in recs) / len(recs)
