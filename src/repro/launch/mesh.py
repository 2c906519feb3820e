"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state.

Single pod: (16, 16) ("data", "model") = 256 chips (one v5e pod).
Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips; the leading
"pod" axis is pure data-parallelism whose collectives cross the
data-center interconnect (gradient all-reduce only — see
optim/compression.py for the int8 cross-pod variant).

Every axis is ``AxisType.Auto``: params, caches and activations are
placed by the declarative rules (sharding/rules.py) and sharding
constraints, and the dispatch runs under ``jax.shard_map``.  JAX's own
``make_mesh`` default, ``Explicit``, would instead type every array by its
sharding and refuse the embedding gather on a model-sharded table.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))
