"""What the pubkey gather must move, the chip's peak bandwidth, and a
module's device time in a profiler trace: the parts of
``pubkeys.gather_hbm_share.slot``.

The bytes are counted from what the gather has to do, never from what
XLA happened to emit: for each key gathered, its (x, y) limbs read from
the table and written into the program's input lanes (2 x 15 limbs x 4
bytes each way), and its 4-byte validator index."""
from benchmark.trace import _DEVICE_PLANE, _MODULES_LINE, _named, _union

# the table's (x, y) of one key: 2 coordinates x 15 limbs x 4 bytes
KEY_BYTES = 120
INDEX_BYTES = 4

# peak HBM bandwidth of one chip, by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 819 GB/s)
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def gather_bytes(keys: int) -> int:
    """Bytes the gather must move for ``keys`` gathered keys."""
    return keys * (2 * KEY_BYTES + INDEX_BYTES)


def hbm_bytes_per_s(device_kind: str) -> float:
    if device_kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no peak HBM bandwidth known for {device_kind!r}")
    return HBM_BYTES_PER_S[device_kind]


def module_device_s(trace_dir: str, module: str):
    """{"device_s", "executions"} of the XLA module ``module`` in the
    trace under ``trace_dir``: the union of its ops' intervals on each
    device plane (summed over the planes), and how many times it ran.
    None where there is no trace or no such module."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir or "", "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    busy_ns, runs = 0, 0
    for plane in ProfileData.from_file(paths[0]).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == _MODULES_LINE:
                modules += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            elif line.name == "XLA Ops":
                ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        runs += sum(m[2].startswith(module) for m in modules)
        mine = [(s, e) for n, s, e in _named(ops, sorted(modules))
                if n.startswith(module)]
        busy_ns += sum(e - s for s, e in _union(mine))
    if not runs:
        return None
    return {"device_s": busy_ns / 1e9, "executions": runs}
