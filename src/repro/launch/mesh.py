"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; callers (dryrun.py)
set ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain the placeholder devices.

Every mesh here has ``Auto`` axes: the sharding rules
(``repro.sharding.rules``) place arrays with ``NamedSharding`` and leave
the layout of intermediates to GSPMD, which ``jax.make_mesh``'s default
``Explicit`` axes refuse (an embedding gather over a vocab-sharded table
raises ``ShardingTypeError``). Enter a mesh with ``jax.set_mesh`` so that
``rules.ambient_mesh`` sees it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_domain_mesh(n_replicas: int = 2, n_shards: int = 2):
    """Small (data, model) mesh for sharded memory domains
    (``core.sharded.ShardedMemoryDomain``): ``data`` carries the
    data-parallel replicas (the PEER_COPY donors), ``model`` the leaf
    shards. Needs ``n_replicas * n_shards`` devices — on CPU, force them
    with ``XLA_FLAGS=--xla_force_host_platform_device_count``."""
    return _auto_mesh((n_replicas, n_shards), ("data", "model"))


def make_mesh(mesh_cfg: MeshConfig):
    return _auto_mesh(mesh_cfg.shape, mesh_cfg.axes)


def mesh_config(multi_pod: bool) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD
