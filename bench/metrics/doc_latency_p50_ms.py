"""Median latency of the documents scheduled in the window, from the
scheduled arrival to the resolution (host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    if run.cell.mix["loop"] != "open" or not lat:
        return None
    return float(np.percentile(lat, 50))
