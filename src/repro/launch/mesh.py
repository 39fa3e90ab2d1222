"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state. Single pod = 16x16 = 256 chips (v5e); multi-pod = 2 pods = 512 chips.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, cp: int = 1):
    """Single pod 16x16 ("data","model"); multi-pod prepends "pod"=2.

    `cp` > 1 trades the "model" axis for a "seq" (context-parallel) axis:
    the 256 chips per pod become (data=256/cp, seq=cp) — fastmax training
    then shards the SEQUENCE over "seq" (`repro.kernels.sharded` seq mode)
    with one constant-size moment exchange per boundary. CP×TP composition
    is deferred (ROADMAP), so cp is exclusive with the "model" axis.
    """
    if cp > 1:
        if 256 % cp:
            raise ValueError(f"cp={cp} must divide the 256 chips of a pod")
        shape = (2, 256 // cp, cp) if multi_pod else (256 // cp, cp)
        axes = ("pod", "data", "seq") if multi_pod else ("data", "seq")
        return _make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-style sharding tests (8 forced host devices)."""
    return _make_mesh(shape, axes)
