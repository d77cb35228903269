"""Explicit CPU runs + the verify plane's mesh provider.

:func:`force_cpu` is for tests and for runs that ask for the CPU on
purpose (the CPU-only bench modes, ``__graft_entry__``'s virtual-device
dryrun): it pins ``JAX_PLATFORMS=cpu``, optionally with n virtual host
devices. Everything else runs on the device JAX finds.

:func:`get_mesh` is the ONE place the process decides whether the verify
plane runs sharded: ``CONSENSUS_SPECS_TPU_MESH=auto|off|<n>`` resolves to a
1-D ``jax.sharding.Mesh`` over the batch axis (ROADMAP item 1 — the DP axis
of the verification batch) or ``None`` for the single-device path. The
serve plane (``serve/service.VerificationService``) acquires it at
construction and threads it through every backend call.
"""
import os
import sys
from typing import Optional

MESH_ENV = "CONSENSUS_SPECS_TPU_MESH"


def force_cpu(n_devices: Optional[int] = None) -> None:
    """Force the CPU platform, optionally with ``n_devices`` virtual devices.

    Must be called before the first device op for the virtual-device count
    to stick (XLA flags are read at backend initialization); a process that
    has imported jax but not yet used a device is pinned through its config.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    if "jax" in sys.modules:
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        try:
            # NB: plain `import jax` does NOT expose jax.extend
            import jax.extend.backend

            jax.extend.backend.clear_backends()
        except Exception:
            pass


def _pow2_floor(n: int) -> int:
    b = 1
    while b * 2 <= n:
        b <<= 1
    return b


def get_mesh(spec: Optional[str] = None):
    """Resolve the process's verify-plane device mesh, or ``None``.

    ``spec`` (default: env ``CONSENSUS_SPECS_TPU_MESH``, unset == ``off``):

    - ``off``/``0``/``1``/empty — single-device path, no mesh (a 1-device
      mesh would only add dispatch overhead);
    - ``auto`` — one 1-D mesh over every visible device (largest
      power-of-two prefix), ``None`` when only one device is visible;
    - ``<n>`` — an n-device mesh. On a CPU platform with jax NOT yet
      imported, :func:`force_cpu` requests n VIRTUAL host devices first
      (``xla_force_host_platform_device_count`` is read once, at backend
      init — so the mesh-bench/smoke entry points call this before any
      device op; an already-initialized process just uses what exists,
      it never clears live backends). Counts clamp to the power-of-two
      floor of what is actually available (the batch rows pad to the
      device count, and the cross-replica butterfly reduction needs a
      power-of-two axis).

    Malformed specs resolve to ``None`` — a typo'd mesh knob must degrade
    to the proven single-device path, never crash service construction.
    The axis is named ``batch``: the only thing sharded is the
    independent-verification batch dimension.
    """
    if spec is None:
        spec = os.environ.get(MESH_ENV, "off")
    spec = spec.strip().lower()
    if spec in ("", "off", "none", "0", "1"):
        return None
    if spec == "auto":
        want = None
    else:
        try:
            want = int(spec)
        except ValueError:
            return None
        if want <= 1:
            return None

    if (want is not None and "jax" not in sys.modules
            and os.environ.get("JAX_PLATFORMS", "") == "cpu"):
        # backend not yet initialized on plain CPU: request the virtual
        # host devices before the first jax import freezes the count.
        # NEVER after — clearing live backends mid-process would
        # invalidate every device reference already handed out.
        force_cpu(n_devices=want)
    import jax

    try:
        have = len(jax.devices())
    except Exception:
        return None
    n = _pow2_floor(have if want is None else min(want, have))
    if n <= 1:
        return None
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("batch",))


def maybe_mesh():
    """``get_mesh()`` that never raises: the serve plane's construction-time
    hook — any mesh-resolution failure means the single-device path, with
    the flight recorder (not an exception) carrying the why."""
    if os.environ.get(MESH_ENV, "off").strip().lower() in (
        "", "off", "none", "0", "1",
    ):
        return None  # fast path: no jax import when the mesh is off
    try:
        return get_mesh()
    except Exception:
        return None
