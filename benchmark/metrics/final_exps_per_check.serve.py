"""Final exponentiations the program paid per check answered in the
serving window (``bls_backend.RLC_STATS['final_exps']`` delta)."""


def read(ctx):
    if not ctx["checks"]:
        return None
    return ctx["window"]["final_exps"] / ctx["checks"]
