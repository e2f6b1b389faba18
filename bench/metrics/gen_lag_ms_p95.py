"""95th percentile of how late the load generator submitted the
documents scheduled in the window (client layer, host clock).  A high
value means the generator, not the server, set the latency."""
import numpy as np


def read(run):
    lag = [1e3 * (s.t_submit - s.t_due) for s in run.due_in_window()]
    return float(np.percentile(lag, 95)) if lag else None
