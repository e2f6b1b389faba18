"""Share of the roofline reached by the Mamba-2 SSD chunk-scan kernels:
the least time the chip needs for the scan FLOPs and bytes that the
launches required (``stage_work``'s ``scan_flops``/``scan_bytes``, true
lengths), against the device time of those kernels (kernels, device
trace).  The bound that applies, FLOPs or bytes, is printed.

Kernels carry no name of their own in the reduced trace, so the scan
kernels are found by position: each step program is matched to its
launch by the server's ``serve.dispatch``/``serve.sync`` spans
(``serve_spans.match_programs``, as ``op_suffix_share`` does), and a
program of a model whose ``arch/<model_type>.py`` lists its Pallas
kernels (``pallas_kernels``, one a layer in layer order) holds that list
once for the operation pass, after it once more for the document pass
when the launch had new tokens.  The trace's first step program is left
out (its first kernels may precede the trace).  A counted program whose
kernel count does not fit the rule makes the reading None."""
import sys

import serve_spans as SS

STEP_PROGRAM = "jit_paged_step"


def scan_programs(run):
    """[(launch record, program seconds, scan-kernel seconds)] of the
    matched step programs of models with a ``pallas_kernels`` list, or
    None when a program breaks the position rule or none is counted."""
    if run.trace is None or not run.launches:
        return None
    t0 = run.trace_span[0]
    programs = [(s, e) for name, s, e in run.trace.module_events
                if name == STEP_PROGRAM]
    recs = sorted(run.launches, key=lambda r: r.index)
    anchors = [SS.LaunchAnchor(i, r.ts_enqueue - t0, r.ts_ready - t0)
               for i, r in enumerate(recs)]
    kernels = run.trace.kernel_events
    models, arch = run.cell.config["models"], run.cell.arch
    out, broken = [], 0
    for a, pos in SS.match_programs(programs, anchors):
        r = recs[a.index]
        kinds_of = getattr(arch[r.model], "pallas_kernels", None)
        if pos == 0 or kinds_of is None:
            continue
        kinds = kinds_of(models[r.model]) * (1 if r.decode_only else 2)
        s, e = programs[pos]
        inside = [k for k in kernels if s <= k[0] <= e and k[1] <= e]
        if len(inside) != len(kinds):
            broken += 1
            continue
        scan = sum(ke - ks for (ks, ke), kind in zip(inside, kinds)
                   if kind == "scan")
        out.append((r, e - s, scan))
    print(f"ssd kernels: {len(out)} step programs counted, {broken} with "
          f"a kernel count off the rule", file=sys.stderr)
    return None if broken or not out else out


def read(run):
    if run.peaks is None:
        return None
    progs = scan_programs(run)
    if progs is None:
        return None
    by_enqueue = {l.t_enqueue: l for l in run.launch_log}
    flops = nbytes = t = 0.0
    for r, _, scan_s in progs:
        launch = by_enqueue.get(r.ts_enqueue)
        if launch is None or "scan_flops" not in launch.required:
            return None
        flops += launch.required["scan_flops"]
        nbytes += launch.required["scan_bytes"]
        t += scan_s
    if t <= 0:
        return None
    t_flops = flops / run.peaks["bf16_flops_per_s"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    bound = "FLOPs" if t_flops >= t_bytes else "bytes"
    print(f"ssd_roofline: {bound}-bound ({t_flops:.4f}s of FLOPs, "
          f"{t_bytes:.4f}s of bytes) over {t:.4f}s of scan-kernel time in "
          f"{len(progs)} launches", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / t
