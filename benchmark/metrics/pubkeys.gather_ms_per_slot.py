"""Milliseconds a slot spends gathering its committees' keys from the
pubkey table: the program's ``pubkeys.gather`` span (dispatch to
completion of the device gather) summed over the window's ``rlc``
records, one record a slot."""
from benchmark import records


def read(ctx):
    window = records.rlc_window(ctx["checks"])
    if not window or any("pubkeys.gather" not in r["spans"] for r in window):
        return None
    return 1e3 * sum(r["spans"]["pubkeys.gather"] for r in window) / len(window)
