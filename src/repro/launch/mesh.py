"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked at first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_page_mesh(n_shards: int = 0):
    """1-D mesh over the serving page axis (``Engine(layout=
    "paged-sharded")``): physical KV/state pages partitioned across the
    devices, everything else replicated.  ``n_shards`` defaults to all
    visible devices."""
    from repro.distributed.sharding_rules import PAGE_AXIS
    n = n_shards or len(jax.devices())
    return make_mesh((n,), (PAGE_AXIS,))
