"""Share of the Mamba-2 model's step-program time spent in its SSD
chunk-scan kernels (model step, device trace): the scan kernels that
``ssd_roofline`` finds by position, over the device time of the step
programs they sit in.  None where ``ssd_roofline``'s rule breaks."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_ssd_roofline_rule",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "ssd_roofline.py"))
_rule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_rule)


def read(run):
    progs = _rule.scan_programs(run)
    if progs is None:
        return None
    total = sum(t for _, t, _ in progs)
    return 100.0 * sum(s for _, _, s in progs) / total if total > 0 else None
