"""Production mesh construction (multi-pod spec).

Defined as functions so importing this module never touches jax device
state.
"""
from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single-pod; (2, 16, 16) pod x data x model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CI-style tests on host placeholder devices."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)

