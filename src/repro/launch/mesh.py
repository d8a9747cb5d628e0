"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any backend initialisation.
"""
from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips, TPU v5e) or 2x16x16 two-pod mesh.

    Axis roles: 'pod' — data-parallel across pods (DCN-linked in a real
    fleet; gradient all-reduce hierarchy reduces intra-pod first);
    'data' — data parallel / ZeRO / FSDP axis; 'model' — tensor parallel.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over host CPU devices (tests / examples)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The composite data-parallel axes of a mesh (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
