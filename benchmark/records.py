"""The program's flush records (``consensus_specs_tpu.obs.tracing``)
that belong to one measured window, for the per-layer readers.

The records live in the program's memory; a reader gets only its
``ctx``, so it picks the window's records by counts the run already
holds. Each function returns None where the program keeps no records,
where the ring no longer reaches back over the whole window, or where the
counts do not land exactly: a reader never folds in a set-up or warm-up
flush, and never reports part of a window."""


def _records():
    try:
        from consensus_specs_tpu.obs import tracing

        return tracing.flush_records()
    except (ImportError, AttributeError):
        return None


def rlc_window(checks):
    """The newest ``rlc`` records whose ``items`` sum to ``checks`` (a
    closed loop whose every flush is one ``batch_verify_rlc`` call)."""
    records = _records()
    if not records or not checks:
        return None
    out, total = [], 0
    for r in reversed(records):
        if r.get("kind") != "rlc":
            continue
        out.append(r)
        total += r["items"]
        if total >= checks:
            break
    return out[::-1] if total == checks else None


def serve_window(serve):
    """(the newest ``serve["device_flushes"]`` serve records, the ``rlc``
    records by id), where those flushes hold ``serve["enqueued"]`` checks
    between them."""
    records = _records()
    if not records or not serve or not serve.get("device_flushes"):
        return None
    flushes = [r for r in records if r.get("kind") == "serve"]
    n = serve["device_flushes"]
    if len(flushes) < n:
        return None
    window = flushes[-n:]
    if sum(r["items"] for r in window) != serve["enqueued"]:
        return None
    rlc = {r["id"]: r for r in records if r.get("kind") == "rlc"}
    return window, rlc


def finalize_s(record):
    """Seconds of one ``rlc`` record in the combine and the final
    exponentiations (easy and hard parts, bisection included)."""
    spans = record["spans"]
    return spans.get("rlc.combine", 0.0) + spans.get("rlc.final_exp", 0.0)
