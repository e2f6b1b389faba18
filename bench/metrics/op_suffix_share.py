"""Share of the step programs' device time spent in the op suffix: from
the start of each ``paged_step`` program's first kernel of the operation
chunk to its end (the operation's extend through the paged flash kernel,
the undo log's restore and the class head), over the programs that ran
whole inside the trace, each matched to its launch (model step, device
trace).

A program is matched to its launch by the server's own ``serve.dispatch``
and ``serve.sync`` spans (``serve_spans.match_programs``), as the launch
timeline holds them (``LaunchRecord.ts_enqueue``/``ts_ready``), carried
onto the trace's clock at the ``bench.window`` span's start.  The op
chunk's first kernel is found by position: the (L + 1)-th Pallas kernel
of a launch with new tokens, the first of a decode-only one, where L is
the model's paged flash kernels in one pass (``paged_attention_layers``
of its ``arch/<model_type>.py``).

The trace's first step program is matched but left out of the share:
the device can start recording operations after that program began (a
v5e trace held 12 of one program's 1,708 kernels).  Any program that
matches no launch, or any other that holds fewer kernels than the
position rule needs, makes the reading None: the match or the rule no
longer holds, and the count on standard error says so."""
import sys

import serve_spans as SS

STEP_PROGRAM = "jit_paged_step"


def read(run):
    if run.trace is None or not run.launches:
        return None
    t0 = run.trace_span[0]
    programs = [(s, e) for name, s, e in run.trace.module_events
                if name == STEP_PROGRAM]
    recs = sorted(run.launches, key=lambda r: r.index)
    anchors = [SS.LaunchAnchor(i, r.ts_enqueue - t0, r.ts_ready - t0)
               for i, r in enumerate(recs)]
    models, arch = run.cell.config["models"], run.cell.arch
    pairs = SS.match_programs(programs, anchors)
    counted = []
    for a, pos in pairs:
        r = recs[a.index]
        if pos > 0:
            layers = arch[r.model].paged_attention_layers(models[r.model])
            counted.append((pos, layers, not r.decode_only))
    suffix, total, skipped = SS.op_suffix_seconds(
        programs, run.trace.kernel_events, counted)
    unmatched = len(programs) - len(pairs)
    print(f"op_suffix_share: {suffix:.4f}s of {total:.4f}s in "
          f"{len(counted) - skipped} step programs; {len(pairs)} of "
          f"{len(programs)} matched, the first left out, {skipped} "
          f"skipped", file=sys.stderr)
    if unmatched or skipped or total <= 0:
        return None
    return 100.0 * suffix / total
