"""Share of the roofline reached by the attention kernels: the least time
the chip needs for the attention FLOPs and KV bytes that the launches
whose step programs ran whole inside the trace required (``work.py``,
true lengths), against the device time of the Pallas kernels inside
those programs (kernels, device trace).  The bound that applies, FLOPs
or bytes, is printed.

The served path's only Pallas kernels are the paged flash (extend) and
paged decode attention kernels; their events carry no name of their own
yet, so they are found as ``tpu_custom_call`` operations."""
import sys


def read(run):
    if run.peaks is None:
        return None
    traced = run.traced_launches()
    t = sum(kernel_s for _, _, kernel_s in traced)
    flops = sum(launch.required["attn_flops"] for launch, _, _ in traced)
    nbytes = sum(launch.required["attn_bytes"] for launch, _, _ in traced)
    if t <= 0 or not flops:
        return None
    t_flops = flops / run.peaks["bf16_flops_per_s"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    bound = "FLOPs" if t_flops >= t_bytes else "bytes"
    print(f"attention_roofline: {bound}-bound ({t_flops:.4f}s of FLOPs, "
          f"{t_bytes:.4f}s of bytes) over {t:.4f}s of kernel time in "
          f"{len(traced)} launches", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / t
