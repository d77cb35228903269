"""Milliseconds a check of the serving window waited outside any stage:
in the ingress queue (submit to the prep stage taking it) and in the
hand-off (prep done to the device stage taking it), summed over the
window's ``serve`` flush records, per check."""
from benchmark import records


def read(ctx):
    found = records.serve_window(ctx.get("serve"))
    if not found:
        return None
    window, _ = found
    wait = sum(r["queue_wait_s"] + r["handoff_wait_s"] for r in window)
    return 1e3 * wait / sum(r["items"] for r in window)
