"""RLC fold and finalization milliseconds per flush over the replay
window: the program's ``rlc.combine`` and ``rlc.final_exp`` spans
(bisection included) summed over the window's ``rlc`` flush records, per
record."""
from benchmark import records


def read(ctx):
    window = records.rlc_window(ctx["checks"])
    if not window:
        return None
    return 1e3 * sum(records.finalize_s(r) for r in window) / len(window)
