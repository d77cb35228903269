"""Keys decompressed on the host per slot in the window: the program's
``host_key_decodes`` counter summed over the window's ``rlc`` records,
one record a slot. With every key in the table it reads 0."""
from benchmark import records


def read(ctx):
    window = records.rlc_window(ctx["checks"])
    if not window or any("host_key_decodes" not in r for r in window):
        return None
    return sum(r["host_key_decodes"] for r in window) / len(window)
