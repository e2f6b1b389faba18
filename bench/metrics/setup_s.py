"""Process start to the first measured instant: weights made on the
device, programs loaded or compiled, warm-up, and the ramp to a steady
loop (host clock)."""


def read(run):
    return run.setup_s
