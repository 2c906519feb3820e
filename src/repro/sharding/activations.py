"""Activation sharding constraints, injected without threading a mesh
through the model code.

``activation_sharding(spec)`` is a context manager holding the
PartitionSpec to constrain the residual stream to at block boundaries;
``constrain(x)`` applies it (no-op outside the context or when the spec's
rank doesn't match).  The dry-run/trainer set it around tracing:

    with mesh, activation_sharding(P(("pod", "data"), None, None)):
        lowered = jax.jit(step, ...).lower(...)

Baseline = batch-sharded residuals; the SP variant (P(dp, "model", None))
shards the sequence over the TP axis between blocks (Megatron-style
sequence parallelism) — a §Perf lever for the memory term.
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar

import jax

_SPEC: ContextVar = ContextVar("activation_spec", default=None)


@contextlib.contextmanager
def activation_sharding(spec):
    tok = _SPEC.set(spec)
    try:
        yield
    finally:
        _SPEC.reset(tok)


def constrain(x: jax.Array) -> jax.Array:
    spec = _SPEC.get()
    if spec is None or len(spec) != x.ndim:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_logits(x: jax.Array) -> jax.Array:
    """(B, S, V) logits: batch like the residual stream, vocab over model."""
    spec = _SPEC.get()
    if spec is None or x.ndim != 3:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(spec[0], None, "model"))


def constrain_tokens(x: jax.Array) -> jax.Array:
    """(B, S) per-token values (labels, losses): batch-sharded."""
    spec = _SPEC.get()
    if spec is None or x.ndim != 2:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(spec[0], None))


def manual_dp_context():
    """(mesh, dp_axes) when tracing under a mesh with an activation spec —
    lets modules (MoE) shard_map themselves over the data axes while the
    model axis stays auto.  (None, ()) outside distributed tracing."""
    spec = _SPEC.get()
    if spec is None or spec[0] is None:
        return None, ()
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return None, ()
    dp = spec[0]
    return mesh, tuple(dp) if isinstance(dp, (tuple, list)) else (dp,)
