"""Production meshes.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and then calls these.

Every axis is `AxisType.Auto`: the partitioner (GSPMD) propagates
shardings through the program, as the feature-sharded consensus path
expects — its contractions over the sharded feature dim reduce with a
psum. JAX's default for `make_mesh` is `Explicit` axes, under which such
a contraction is a type error.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips; the "pod"
    axis composes with "data" for batch / consensus-agent sharding."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the host's actual devices (tests / smoke runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that jointly shard the batch / consensus-agent dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def num_agents(mesh) -> int:
    """Number of consensus agents = product of batch axes."""
    import math
    return math.prod(mesh.shape[a] for a in batch_axes(mesh))
