"""Final exponentiations the program paid per check verified in the
window (``bls_backend.RLC_STATS['final_exps']`` delta)."""


def read(ctx):
    if not ctx["checks"]:
        return None
    return ctx["window"]["final_exps"] / ctx["checks"]
