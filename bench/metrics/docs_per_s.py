"""Documents resolved in the window per second of the window (host
clock).  The closed loop's window is a column job of whole blocks, from
the first submit to the last resolution."""


def read(run):
    if run.cell.mix["loop"] != "closed":
        return None
    return len(run.in_window()) / run.window_s
