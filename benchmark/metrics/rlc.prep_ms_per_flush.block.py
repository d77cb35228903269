"""Host-prep milliseconds per RLC flush over the replay window: the
program's ``rlc.prep`` span (codec passes, limb staging, scatter, up to
the Miller program) summed over the window's ``rlc`` flush records, per
record."""
from benchmark import records


def read(ctx):
    window = records.rlc_window(ctx["checks"])
    if not window:
        return None
    return 1e3 * sum(r["spans"].get("rlc.prep", 0.0)
                     for r in window) / len(window)
