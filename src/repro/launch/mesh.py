"""Production mesh factory.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The dry-run entry point
(launch/dryrun.py) sets ``XLA_FLAGS=--xla_force_host_platform_device_count
=512`` *before* any jax import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def _auto(n_axes: int):
    """``Auto`` axis types: the model code places arrays with
    ``with_sharding_constraint``, which ``make_mesh``'s default
    ``Explicit`` axes refuse."""
    from jax.sharding import AxisType

    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """The target mesh: one v5e pod = (data=16, model=16) = 256 chips;
    multi-pod = (pod=2, data=16, model=16) = 512 chips with pure-DP across
    the `pod` axis (DCN-crossing collectives are gradient all-reduce only).
    """
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: Optional[int] = None):
    """A mesh over whatever devices actually exist (tests / examples)."""
    import jax

    n = len(jax.devices())
    model = model or 1
    if model <= 0 or n % model != 0:
        raise ValueError(
            f"cannot build a (data={n}//{model}, model={model}) mesh: the "
            f"model-parallel degree must be a positive divisor of the "
            f"{n} available device(s); pick a divisor of {n} or use "
            f"make_sweep_mesh() for 1-D batch sharding")
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def make_sweep_mesh(n_items: Optional[int] = None):
    """A 1-D ``("data",)`` mesh for batch-sharded Monte-Carlo sweeps.

    Picks the largest usable device count: all devices, capped at
    ``n_items`` when given — sharding a chunk smaller than the machine
    across every device would leave devices with zero rows, which
    ``shard_map`` cannot express; capping instead lets uneven chunks pad up
    to the next multiple of the mesh size (see repro.sweeps.shard).
    """
    import jax

    n = len(jax.devices())
    d = n if n_items is None else max(1, min(int(n_items), n))
    return jax.make_mesh((d,), ("data",), axis_types=_auto(1))


def batch_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def mesh_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
