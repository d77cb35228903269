"""Seconds in blocked ``vm.execute`` calls (the program's ``vm[...]``
profiling labels) over the traced replay window; the rest is host work."""


def read(ctx):
    if not ctx["traced_s"] or not ctx["vm_s"]:
        return None
    return 100.0 * ctx["vm_s"] / ctx["traced_s"]
