"""RLC fold and finalization milliseconds per flush over the serving
window: the ``rlc.combine`` and ``rlc.final_exp`` spans of the ``rlc``
records that the window's ``serve`` flush records name, per record."""
from benchmark import records


def read(ctx):
    found = records.serve_window(ctx.get("serve"))
    if not found:
        return None
    window, rlc = found
    named = [rlc.get(r["rlc"]) for r in window]
    if not named or any(r is None for r in named):
        return None
    return 1e3 * sum(records.finalize_s(r) for r in named) / len(named)
