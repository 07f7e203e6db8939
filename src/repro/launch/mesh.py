"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first jax init.
Every mesh here uses Auto axis types (``jax.make_mesh`` defaults to Explicit):
the pjit paths place their arrays with ``NamedSharding`` and let XLA
propagate the rest.  The ring's mesh is ``core.pipeline.make_ring_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips; multi-pod adds pod=2 => 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def require_devices(n: int) -> None:
    devs = jax.devices()
    if len(devs) >= n:
        return
    platform = devs[0].platform
    msg = f"need {n} devices, found {len(devs)} on platform {platform!r}."
    if platform == "cpu":
        msg += (f" Set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                f"BEFORE importing jax (dryrun.py does this automatically).")
    else:
        msg += f" Run on a host with at least {n} {platform} devices."
    raise RuntimeError(msg)
