"""95th percentile of how long a document waited in the server's ready
queue before a launch took it: from the instant it became ready (its
admission at submit, the end of its previous stage, or the end of a
retry's backoff) to the start of its launch's ``serve.dispatch`` span,
over the window's launches (scheduler, program span:
``LaunchRecord.queue_wait_s``).  A client's lag in submitting is the
client layer's (``gen_lag_ms_p95``), not this one's.  A program without
the field reads nothing."""
import numpy as np


def read(run):
    waits = [w for r in run.launches for w in getattr(r, "queue_wait_s", ())]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
