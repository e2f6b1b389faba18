"""The whole stage step's share of the chip's bf16 peak: the FLOPs that
the launches whose step programs ran whole inside the trace required
(``work.py``, true lengths), over those programs' device time (model
step, device trace).  Read as ``mfu.column`` and ``mfu.open``."""


def read(run):
    if run.peaks is None:
        return None
    traced = run.traced_launches()
    t = sum(dev_s for _, dev_s, _ in traced)
    flops = sum(launch.required["flops"] for launch, _, _ in traced)
    if t <= 0 or not flops:
        return None
    return 100.0 * flops / t / run.peaks["bf16_flops_per_s"]
