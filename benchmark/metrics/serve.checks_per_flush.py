"""Checks the service enqueued per device flush over the window (its
metrics snapshot: ``enqueued`` / ``device_flushes``)."""


def read(ctx):
    s = ctx["serve"]
    if not s or not s["device_flushes"]:
        return None
    return s["enqueued"] / s["device_flushes"]
