"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

A FUNCTION, not a module constant — importing this module never touches jax
device state (dryrun.py sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

__all__ = [
    "make_auto_mesh", "make_production_mesh", "make_debug_mesh",
    "make_controller_mesh", "HARDWARE",
]

# TPU v5e-class constants used by the roofline analysis (launch/roofline.py).
HARDWARE = {
    "peak_flops_bf16": 197e12,  # per chip, FLOP/s
    "hbm_bandwidth": 819e9,  # per chip, B/s
    "ici_link_bandwidth": 50e9,  # per link, B/s
    "hbm_bytes": 16 * 1024**3,  # per chip
}


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode.

    ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (``models/sharding.constrain``) rejects; every
    mesh in this repo is an Auto mesh.
    """
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (shard_map paths exercise on 1 device)."""
    return make_auto_mesh((data, model), ("data", "model"))


def make_controller_mesh(n_shards: int | None = None):
    """1-D ``("data",)`` mesh over the controller's local devices.

    The mesh the sharded aggregation arena lays its ``(n_max, P)`` buffer out
    on (``core/store.ArenaStore(mesh=...)``): ``P`` splits over ``data``, rows
    are replication-free, and every row write / masked reduction stays
    collective-free.  ``n_shards`` defaults to every visible device; pass 1
    for a single-device smoke mesh (identical numerics, same code path).
    """
    import jax

    n = int(n_shards) if n_shards else len(jax.devices())
    return make_auto_mesh((n,), ("data",))
