"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

The helpers are FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS *before* the first jax device query, while smoke tests must
keep seeing 1 device.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh whose axes are all ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` and jit-without-a-mesh-context are refused;
    the sharding rules, the pipeline and the sharded serving path are all
    written for compiler-propagated (``Auto``) shardings.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def best_effort_mesh(model_parallel: int = 1):
    """Elastic helper: build the largest (data, model) mesh the *currently
    alive* devices support. Used by the fault-tolerant driver when restarting
    after losing hosts: model_parallel is fixed by the checkpoint layout, the
    data axis absorbs whatever is left."""
    n = jax.device_count()
    if n % model_parallel != 0:
        raise ValueError(f"{n} devices not divisible by TP={model_parallel}")
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
