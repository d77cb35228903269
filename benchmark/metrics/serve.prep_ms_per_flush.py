"""Host-prep milliseconds per flush over the window (the service's
metrics snapshot: ``prep_ms_total`` / ``prep_batches``)."""


def read(ctx):
    s = ctx["serve"]
    if not s or not s["prep_batches"]:
        return None
    return s["prep_ms_total"] / s["prep_batches"]
