"""Share of the traced replay window in which no operation ran on the
device: 1 - (union of device-op intervals / window), from the trace."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
