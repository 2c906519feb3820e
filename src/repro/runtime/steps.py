"""Step functions — the compilation units of the whole system.

``make_train_step(cfg)``   -> (state, batch) -> (state, metrics)
``make_prefill_step(cfg)`` -> (params, batch) -> (last_logits, cache)
``make_decode_step(cfg)``  -> (params, cache, inputs) -> (logits, cache)

train_step = microbatched fwd+bwd (lax.scan gradient accumulation when
cfg-level ``grad_accum > 1``) + global-norm clip + cosine LR + AdamW.
All functions are pure and jit-friendly; sharding is applied by the caller
(launch/dryrun.py, runtime/trainer.py) via in_shardings/out_shardings.

The decode/chunk steps are cache-layout agnostic: a paged KV cache
(models/model.init_cache with page_size > 0) rides through the same
``cache`` pytree — pooled ``k``/``v`` leaves plus a ``block_table`` —
so the step signatures and their compiled-once contract are unchanged.
The server mutates the block table HOST-side and refreshes the traced
leaf each tick (same shape/dtype always -> zero retraces).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule


def init_train_state(key, cfg: ModelConfig):
    params = M.init_model(key, cfg)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(cfg: ModelConfig, *, grad_accum: int = 1,
                    base_lr: float = 3e-4, warmup: int = 200,
                    total_steps: int = 10_000, max_grad_norm: float = 1.0):
    """Returns train_step(state, batch)->(state, metrics).  ``batch`` =
    {"inputs": (B, S)[, d], "labels": (B, S)}; B must divide by grad_accum."""

    def loss_fn(params, inputs, labels):
        return M.lm_loss(cfg, params, inputs, labels)

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch["inputs"], batch["labels"])
        else:
            # microbatch accumulation: scan over grad_accum slices of B.
            # Per-microbatch metrics ride the scan ys and are meaned over
            # the accumulation axis — equal-sized slices, so the mean of
            # per-slice means equals the full-batch value for every
            # token-meaned metric (invocation, router_acc, lm_loss, the
            # per-class dispatch vectors...); they used to be silently
            # dropped whenever grad_accum > 1.
            def mb(carry, sl):
                acc, lsum = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, sl["inputs"], sl["labels"])
                return (jax.tree.map(jnp.add, acc, g), lsum + l), m
            slices = jax.tree.map(
                lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum,
                                    *a.shape[1:]), batch)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), ms = jax.lax.scan(mb, (zeros, 0.0), slices)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = lsum / grad_accum
            metrics = jax.tree.map(lambda v: jnp.mean(v, axis=0), ms)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = cosine_schedule(state["step"], base_lr=base_lr, warmup=warmup,
                             total=total_steps)
        params, opt = adamw_update(params, grads, state["opt"], state["step"],
                                   lr=lr)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        logits, cache, _, _ = M.forward(cfg, params, batch["inputs"],
                                        collect_cache=True, serve=True)
        return logits[:, -1], cache
    return prefill_step


def mcma_serve_config(cfg: ModelConfig, *, backend: str | None = None) -> ModelConfig:
    """Serve-mode cfg routing the ApproxFFN through the MCMA weight-switch
    dispatch engine (runtime/dispatch.py).  Default backend is the Pallas
    kernel; ``backend="pallas_fused"`` runs the gather/scatter-fused
    kernel; ``backend="xla"`` swaps in the pure-XLA dispatch — the
    oracle the benches gate both kernels against.

    The Pallas kernels are TPU kernels: they compile on ``"tpu"`` and run
    in the Pallas interpreter on ``"cpu"`` (tests, CI).  Any other
    platform raises rather than silently interpreting."""
    assert cfg.approx.enable, "MCMA dispatch requires cfg.approx.enable"
    backend = backend or "pallas"
    from repro.runtime.dispatch import PALLAS_BACKENDS
    interpret = False
    if backend in PALLAS_BACKENDS:
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"dispatch backend {backend!r} runs Pallas TPU kernels: "
                f"compiled on 'tpu', interpreted on 'cpu'; found "
                f"platform {platform!r} — serve with backend='xla' there")
        interpret = platform == "cpu"
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, backend=backend, interpret=interpret))


@contextlib.contextmanager
def serve_mesh_context(mesh):
    """Trace/serve context for mesh deployments.

    Activates the mesh plus batch-sharded activations so the serve-mode
    modules (ApproxFFN, MoE) detect the distributed deployment
    (sharding/activations.manual_dp_context) and take their
    shard_map-native dispatch paths — the MCMA engine per data shard with
    psum-reduced invoke_stats.  ``mesh=None`` is a no-op so single-device
    callers share the same code path.  Must wrap the call that TRACES the
    jitted step (jit traces lazily, so wrapping every call is the safe
    pattern — the context is cheap after the first).
    """
    if mesh is None:
        yield None
        return
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules as R
    from repro.sharding.activations import activation_sharding
    with mesh, activation_sharding(P(R.dp_axes(mesh), None, None)):
        yield mesh


def _serve_cfg(cfg: ModelConfig, *, use_mcma_dispatch: bool,
               operating_point, route_scope: str | None,
               backend: str | None) -> ModelConfig:
    """Shared cfg munging for the serve-mode steps (decode + prefill
    chunk): MCMA backend selection, route-scope override, operating-point
    capacity replacement.  Both steps MUST come out of the same cfg or
    the prefill chunk and the decode tick would disagree on dispatch."""
    if use_mcma_dispatch:
        cfg = mcma_serve_config(cfg, backend=backend)
    if route_scope is not None:
        if route_scope not in ("layer", "tick"):
            raise ValueError(f"unknown route_scope: {route_scope!r} "
                             "(expected 'layer' or 'tick')")
        cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, route_scope=route_scope))
    if operating_point is not None:
        pt = operating_point
        cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, exact_frac=pt.exact_frac,
            invoke_frac=pt.invoke_frac, shard_slack=pt.shard_slack,
            invoke_fracs=tuple(pt.invoke_fracs),
            tier_margins=tuple(pt.tier_margins) or cfg.approx.tier_margins))
    return cfg


def make_decode_step(cfg: ModelConfig, *, use_mcma_dispatch: bool = False,
                     with_stats: bool = False, operating_point=None,
                     route_scope: str | None = None,
                     backend: str | None = None):
    """``use_mcma_dispatch`` swaps the serve-mode FFN engine to the MCMA
    Pallas dispatch; ``with_stats`` makes the step also return the
    layer-meaned dispatch metrics (invocation rate etc.) per tick.

    ``operating_point`` (runtime/autotune.OperatingPoint) overrides the
    config's serve capacity fractions — capacities are SHAPES, so each
    ladder rung is its own compilation unit; the server precompiles one
    step per rung and the autotuner switches between them (never
    retraces a live one).

    ``route_scope`` overrides ``cfg.approx.route_scope``: "tick" makes
    the step route ONCE per tick (one DispatchPlan from the tick-router
    head, hoisted above the layer scan and reused by every layer — the
    paper's per-input decision); "layer" keeps per-layer routing.  Under
    ``with_stats`` a tick-scope step's metrics are the single tick-level
    observation (every layer reports the same plan), so an autotuner
    consumes one exact sample per tick instead of L noisy per-layer ones.

    The returned step takes an optional trailing ``row_mask`` ((B,) bool
    of ACTIVE slots); pass it on partially-full slot tables so idle rows
    never bias the dispatch stats (the free-slot router-bias fix).  It
    also takes optional ``tier`` ((B,) int32 per-slot QoS tier) +
    ``tier_margins`` ((n_tiers,) float32) — both TRACED inputs, so one
    compiled step serves every tier mix and margin setting; only the
    capacity fields of an operating point (shapes) force a recompile.

    The optional trailing ``residency`` ((n_resident,) int32 library
    class ids, library configs only) is likewise TRACED — the
    ResidencyController swaps the hot set by feeding a new vector
    through the SAME compiled step, zero retraces.

    ``backend`` (with ``use_mcma_dispatch``) overrides the dispatch
    backend: default "pallas", "pallas_fused" for the gather/scatter-
    fused kernel, or "xla" for the oracle engine."""
    cfg = _serve_cfg(cfg, use_mcma_dispatch=use_mcma_dispatch,
                     operating_point=operating_point,
                     route_scope=route_scope, backend=backend)

    def decode_step(params, cache, inputs, row_mask=None, tier=None,
                    tier_margins=None, residency=None):
        return M.decode(cfg, params, cache, inputs, serve=True,
                        collect_metrics=with_stats, row_mask=row_mask,
                        tier=tier, tier_margins=tier_margins,
                        residency=residency)
    return decode_step


def make_prefill_chunk_step(cfg: ModelConfig, *, use_mcma_dispatch: bool = False,
                            with_stats: bool = False, operating_point=None,
                            route_scope: str | None = None,
                            backend: str | None = None):
    """Chunked-prefill step: consume up to S prompt tokens per slot into
    the SAME decode cache layout ``make_decode_step`` advances, without
    computing logits (the final prompt token always goes through the
    decode step, so the first sampled token is bit-identical to
    token-by-token prefill).

    Signature: ``prefill_chunk_step(params, cache, tokens, n_valid,
    row_mask=None, tier=None, tier_margins=None) -> (cache, metrics)``
    with ``tokens`` (B, S) int32 right-padded per row and ``n_valid``
    (B,) int32 counting real tokens (0 = slot not prefilling this tick —
    nothing is written for that row).  KV writes use scatter-with-drop
    indexing, so a row can never clamp-corrupt the last cache position.

    Shares ``_serve_cfg`` with ``make_decode_step`` so both phases run
    the identical dispatch configuration; chunk-phase dispatch metrics
    come back under the same keys but must be accumulated SEPARATELY
    from decode ticks (the autotuner's signal is decode-phase only).
    Uniform (dense-attention) models only — SSM/hybrid/sliding-window
    caches are not positionally addressable, the server falls back to
    token-by-token feeding for those."""
    cfg = _serve_cfg(cfg, use_mcma_dispatch=use_mcma_dispatch,
                     operating_point=operating_point,
                     route_scope=route_scope, backend=backend)

    def prefill_chunk_step(params, cache, tokens, n_valid, row_mask=None,
                           tier=None, tier_margins=None, residency=None):
        return M.decode_chunk(cfg, params, cache, tokens, n_valid,
                              serve=True, collect_metrics=with_stats,
                              row_mask=row_mask, tier=tier,
                              tier_margins=tier_margins,
                              residency=residency)
    return prefill_chunk_step
