"""Share of the reserved KV arena bytes that hold live documents' true
tokens, read right after each launch of the window is dispatched, while
its documents hold their rows, and averaged over the launches (data
plane, program counters; traced runs only)."""


def read(run):
    samples = [l.arena for l in run.launched_in_window()
               if l.arena is not None and l.arena[0] > 0]
    if not samples:
        return None
    return 100.0 * sum(live / r for r, live in samples) / len(samples)
