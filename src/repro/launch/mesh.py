"""Production mesh construction (function, never module-level state).

Single pod : (16, 16)    axes ("data", "model")          — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model")   — 512 chips

Data parallelism spans ("pod","data") on the multi-pod mesh; the "model" axis
carries TP / vocab / expert sharding and stays inside a pod (ICI, not DCN).
"""

from __future__ import annotations

from repro.distrib.sharding import make_mesh
from repro.models.common import MeshPolicy


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale dry-run tests (needs >= prod(shape) devices)."""
    return make_mesh(shape, axes)


def policy_for(mesh) -> MeshPolicy:
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return MeshPolicy(mesh=mesh, dp=dp, tp="model")
