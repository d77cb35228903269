"""The pubkey gather's share of the chip's HBM roofline, in %: the bytes
it must move (``roofline.gather_bytes`` of the keys it gathered in the
traced slots, from their ``rlc`` records' ``keys_gathered``) over the
device time of its ``jit_pubkey_gather`` ops in the trace, over the
chip's peak bandwidth."""
from benchmark import records, roofline


def read(ctx):
    trace = ctx.get("gather_trace")
    traced = ctx.get("slots_traced", 0)
    if not trace or not trace["device_s"] or trace["executions"] != traced:
        return None
    window = records.rlc_window(ctx["checks"])
    if not window or any("keys_gathered" not in r for r in window[:traced]):
        return None
    keys = sum(r["keys_gathered"] for r in window[:traced])
    if not keys:
        return None
    return (100.0 * roofline.gather_bytes(keys) / trace["device_s"]
            / roofline.hbm_bytes_per_s(ctx["device_kind"]))
