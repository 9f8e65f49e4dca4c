"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialisation.

Production topology (TPU v5e): 16 x 16 = 256 chips per pod; multi-pod adds a
leading ``pod`` axis over the data-centre interconnect.  Axis roles:
``data`` = FSDP/DP, ``model`` = TP/EP/SP, ``pod`` = pure DP (gradient
all-reduce only crosses pods — DESIGN.md §5).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "describe"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh for tests/examples (e.g. (1, 1) on one CPU)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def describe(mesh: Mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
