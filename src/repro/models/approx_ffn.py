"""ApproxFFN — the paper's MCMA generalized to a first-class LM layer.

Semantics (DESIGN.md §4): the exact FFN is the target function ("CPU"
path); ``n_approx`` small identical-topology tanh MLPs are the
approximators; an (n+1)-way router is the multiclass classifier (class 0 =
exact).  Co-training follows the paper's competitive scheme: per-token
relative L2 error of each approximator against the exact FFN output defines
the label (argmin error if under the bound, else class 0), the router trains
on those labels (xent) and each approximator distills on its territory.

Two execution modes, both shape-static:

* ``train``: exact FFN for every token (teacher) + all approximators on all
  tokens (errors/labels need them anyway).  Output = exact FFN (training is
  never approximated); aux losses carry the co-training signal.
* ``serve``: MoE-style capacity dispatch.  Tokens are routed by the
  router's argmax; class 0 tokens go through the exact FFN (capacity
  ``exact_frac``·T), classes 1..n through their approximator (capacity
  ``invoke_frac``·T each).  Over-capacity tokens contribute zero (residual
  carries them) — the GShard convention.  FLOP savings vs a dense FFN =
  1 - exact_frac (approximator FLOPs are ~d_hidden/d_ff of the FFN's).

The serve-mode grouped approximator matmul is exactly the access pattern of
the Pallas ``switched_mlp`` kernel (kernels/switched_mlp.py): rows sorted by
class, per-tile weight switch via scalar prefetch.  The XLA path here is the
portable fallback; the kernel is used by ops.switched_apply for 2D token
batches on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ModelConfig
from repro.kernels.ops import prepad_switched_weights
from repro.models.layers import ffn_fwd, init_ffn


def init_approx_ffn(key, cfg: ModelConfig):
    d, a = cfg.d_model, cfg.approx
    n = a.n_live                 # full LIBRARY when one is configured
    ks = jax.random.split(key, 4)
    s_in, s_h = d ** -0.5, a.d_hidden ** -0.5
    # stacked identical-topology approximators (paper §III-D requirement),
    # stored in SERVING form from the start: the zero-weight nC
    # pseudo-class appended and feature dims lane-padded
    # (kernels/ops.prepad_switched_weights), so the decode hot path ships
    # the stacks into the weight-switch kernel with no per-call copies.
    # With a library (a.library_size > 0) the stacks hold ALL library_size
    # approximators and the router head covers the full library — at serve
    # time ops.gather_resident_stacks pulls the resident rows out.
    # Padded regions are exact zeros and STAY zero under training: the
    # train path only reads/derives gradients through the logical views
    # (approx_stacks), so their grads — and hence AdamW updates — are zero.
    w1 = jax.random.normal(ks[2], (n, d, a.d_hidden), cfg.pdtype) * s_in
    b1 = jnp.zeros((n, a.d_hidden), cfg.pdtype)
    w2 = jax.random.normal(ks[3], (n, a.d_hidden, d), cfg.pdtype) * s_h
    b2 = jnp.zeros((n, d), cfg.pdtype)
    w1, b1, w2, b2 = prepad_switched_weights(w1, b1, w2, b2)
    return {"ffn": init_ffn(ks[0], cfg),
            "router": jax.random.normal(ks[1], (d, n + 1),
                                        cfg.pdtype) * s_in,
            "a_w1": w1, "a_b1": b1, "a_w2": w2, "a_b2": b2}


def approx_stacks(cfg: ModelConfig, p):
    """Logical (n_live, d, d_hidden)-shaped views of the serving-form
    stacks — what the train path and error labelling operate on."""
    a, d = cfg.approx, cfg.d_model
    n = a.n_live
    return (p["a_w1"][:n, :d, :a.d_hidden],
            p["a_b1"][:n, :a.d_hidden],
            p["a_w2"][:n, :a.d_hidden, :d],
            p["a_b2"][:n, :d])


def _apply_all_approx(cfg, p, x):
    """All approximators on all tokens.  x: (T, d) -> (n, T, d)."""
    w1, b1, w2, b2 = approx_stacks(cfg, p)
    h = jnp.einsum("td,ndh->nth", x, w1.astype(x.dtype))
    h = jnp.tanh(h + b1[:, None, :].astype(x.dtype))
    y = jnp.einsum("nth,nhd->ntd", h, w2.astype(x.dtype))
    return y + b2[:, None, :].astype(x.dtype)


def _rel_err(y_hat, y, eps=1e-6):
    """Per-token relative L2 error (competitive-scheme label signal)."""
    num = jnp.linalg.norm((y_hat - y).astype(jnp.float32), axis=-1)
    den = jnp.linalg.norm(y.astype(jnp.float32), axis=-1)
    return num / jnp.maximum(den, eps)


def approx_ffn_train(cfg: ModelConfig, p, x: jax.Array):
    """Training path.  x: (B, S, d) -> (exact FFN out, aux dict).

    aux = {"loss": distill + router xent (weighted), "invocation": fraction
    of tokens whose best approximator is under the bound}.
    """
    a = cfg.approx
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    exact = ffn_fwd(cfg, p["ffn"], xt)                      # (T, d) teacher
    approx = _apply_all_approx(cfg, p, xt)                  # (n, T, d)
    errs = jax.vmap(lambda yh: _rel_err(yh, exact))(approx)  # (n, T)

    # competitive labels: argmin error if under bound, else 0 (exact)
    best = jnp.argmin(errs, axis=0)
    safe = errs.min(0) <= a.error_bound
    labels = jnp.where(safe, best + 1, 0)                   # 0 = exact path

    logits = jnp.dot(xt, p["router"].astype(xt.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    router_loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    # distillation: each approximator fits its territory (stop-grad teacher)
    tgt = jax.lax.stop_gradient(exact.astype(jnp.float32))
    own = jax.nn.one_hot(labels - 1, a.n_live, axis=0) * safe   # (n, T)
    sq = jnp.sum((approx.astype(jnp.float32) - tgt[None]) ** 2, -1)  # (n, T)
    # territory tokens at weight 1; all tokens at small weight (exploration)
    w = own + 0.05
    distill = jnp.sum(sq * w) / jnp.maximum(jnp.sum(w), 1.0) / d

    aux = {"loss": a.router_weight * router_loss + a.distill_weight * distill,
           "invocation": jnp.mean(safe.astype(jnp.float32)),
           "router_acc": jnp.mean((jnp.argmax(logits, -1) == labels)
                                  .astype(jnp.float32)),
           # per-token one-hot competitive labels — the model (model.py)
           # sums these over the layer scan to train the TICK router head
           # on the across-layer modal label (route_scope="tick")
           "label_votes": jax.nn.one_hot(labels, a.n_live + 1,
                                         dtype=jnp.float32)}
    return exact.reshape(b, s, d), aux


def _manual_serve_ctx(cfg: ModelConfig, b: int):
    """(mesh, dp, n_data_shards) when the shard_map-native serve path
    engages for a batch of ``b`` rows under the active distributed trace
    context, else (None, (), 1).  The SAME predicate gates plan
    construction (make_tick_plan) and per-layer consumption, so a tick
    plan is always built with exactly the sharding its consumers expect."""
    from repro.sharding.activations import manual_dp_context
    mesh, dp = manual_dp_context()
    if mesh is None or "model" not in mesh.axis_names:
        return None, (), 1
    import numpy as _np
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    g = int(_np.prod([sizes[ax] for ax in dp]))
    if b % g == 0 and cfg.d_ff % sizes["model"] == 0:
        return mesh, dp, g
    return None, (), 1


def serve_caps(cfg: ModelConfig, t_local: int):
    """(exact_cap, invoke_cap) for a shard of ``t_local`` rows — the ONE
    place the config's capacity fractions become row budgets.  With
    ``approx.invoke_fracs`` set (asymmetric per-class capacities, e.g.
    from runtime/autotune.ladder_from_counts) ``invoke_cap`` is the
    per-class tuple the engine accepts."""
    from repro.sharding.rules import shard_capacity
    a = cfg.approx
    ec = shard_capacity(t_local, a.exact_frac, slack=a.shard_slack)
    if a.invoke_fracs:
        assert len(a.invoke_fracs) == a.n_approx, \
            (a.invoke_fracs, a.n_approx)
        return ec, tuple(shard_capacity(t_local, f, slack=a.shard_slack)
                         for f in a.invoke_fracs)
    return ec, shard_capacity(t_local, a.invoke_frac, slack=a.shard_slack)


def _default_margins(cfg: ModelConfig) -> jax.Array:
    """The config's static per-tier margin fallback (zeros when unset) —
    the ONE definition every serve path defaults from when a caller
    passes tiers without a margins vector."""
    a = cfg.approx
    return jnp.asarray(a.tier_margins or (0.0,) * a.n_tiers, jnp.float32)


def _row_mask_tokens(row_mask, s: int):
    """Normalize an active mask to per-ROW (B*S,) bools.  Accepts the
    server's per-slot (B,) mask (every token of a slot shares its state)
    or a chunked-prefill TOKEN mask (B, S) (each slot live only up to its
    ``n_valid`` tokens this chunk)."""
    if row_mask is None:
        return None
    rm = row_mask.astype(bool)
    return rm.reshape(-1) if rm.ndim == 2 else jnp.repeat(rm, s)


def _tier_args(cfg: ModelConfig, tier, tier_margins, s: int):
    """Normalize the per-slot QoS args for an (B, S) row batch: expand the
    (B,) tier vector to (B*S,) rows and default the margins vector from
    the config when the caller passed tiers without one."""
    if tier is None:
        return None, None
    tr = jnp.repeat(tier.astype(jnp.int32), s)
    if tier_margins is None:
        tier_margins = _default_margins(cfg)
    return tr, tier_margins


@jax.named_scope(scopes.MCMA_PLAN)
def make_tick_plan(cfg: ModelConfig, params, x: jax.Array,
                   row_mask: jax.Array | None = None,
                   tier: jax.Array | None = None,
                   tier_margins: jax.Array | None = None,
                   residency: jax.Array | None = None):
    """One DispatchPlan per decode tick (route_scope="tick").

    Classifies with the model's TICK-router head (``params["tick_router"]``,
    co-trained on the across-layer competitive labels) on the pre-layer
    hidden state ``x`` (B, S=1, d), runs capacity + class-sort once, and
    returns the plan every layer of the decode scan executes against.
    ``tier`` ((B,) int32 per-slot QoS tier) + ``tier_margins`` ((n_tiers,)
    traced) apply the per-request exact-logit margins to the ONE tick
    decision, so a mixed-tier batch routes each row at its own quality
    bound; the plan then carries the per-tier invoke-stat split for every
    layer.  ``residency`` ((n_resident,) int32 library ids, TRACED) folds
    full-library routing onto the resident slots (the tick-router head is
    library-wide when ``approx.library_size`` is set); the per-layer
    executors then run against residency-gathered stacks of the same
    slot count.  Under a distributed trace context the plan is built per
    data shard inside a shard_map — the identical sharding the per-layer
    manual serve path consumes it with — and its count fields are
    psum-reduced to global totals, so the autotuner (and the
    ResidencyController, via ``lib_counts``) reads ONE exact observation
    per tick.
    """
    from repro.runtime.dispatch import make_dispatch_plan
    a = cfg.approx
    b, s, d = x.shape
    t = b * s
    assert "tick_router" in params, (
        "route_scope='tick' needs the tick-router head, but these params "
        "have none — they predate the head (init_model now adds "
        "'tick_router' whenever approx.enable); re-init or serve with "
        "route_scope='layer'")
    router = params["tick_router"]
    mesh, dp, g = _manual_serve_ctx(cfg, b)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import dispatch_plan_specs
        tl = t // g
        ec, ic = serve_caps(cfg, tl)
        if row_mask is None:
            row_mask = jnp.ones((b,), bool)
        mask2d = row_mask.ndim == 2
        has_tier = tier is not None
        if has_tier and tier_margins is None:
            tier_margins = _default_margins(cfg)
        nt = int(tier_margins.shape[0]) if has_tier else 1
        has_res = residency is not None

        def local(rt, x_l, m_l, *extra):
            extra = list(extra)
            t_l, tm = (extra.pop(0), extra.pop(0)) if has_tier \
                else (None, None)
            res = extra.pop(0) if has_res else None
            bl, sl, _ = x_l.shape
            xt = x_l.reshape(bl * sl, d)
            lg = jnp.dot(xt, rt.astype(xt.dtype)).astype(jnp.float32)
            return make_dispatch_plan(
                lg, _row_mask_tokens(m_l, sl), exact_cap=ec,
                invoke_cap=ic, backend=a.backend, block_t=a.block_t,
                stats_axes=dp,
                tier=None if t_l is None else jnp.repeat(t_l, sl),
                tier_margins=tm, residency=res)

        in_specs = (P(None, None), P(dp, None, None),
                    P(dp, None) if mask2d else P(dp))
        args = (router, x, row_mask)
        if has_tier:
            in_specs = in_specs + (P(dp), P(None))
            args = args + (tier.astype(jnp.int32), tier_margins)
        if has_res:
            in_specs = in_specs + (P(None),)
            args = args + (residency.astype(jnp.int32),)
        fn = jax.shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=dispatch_plan_specs(
                mesh, data_axes=dp,
                n_approx=int(residency.shape[0]) if has_res else a.n_approx,
                exact_cap=ec, invoke_cap=ic, block_t=a.block_t,
                backend=a.backend, n_tiers=nt,
                library_size=a.library_size if has_res else 0),
            axis_names=frozenset(tuple(dp) + ("model",)), check_vma=False)
        return fn(*args)

    xt = x.reshape(t, d)
    logits = jnp.dot(xt, router.astype(xt.dtype)).astype(jnp.float32)
    rm = _row_mask_tokens(row_mask, s)
    tr, tier_margins = _tier_args(cfg, tier, tier_margins, s)
    ec, ic = serve_caps(cfg, t)
    return make_dispatch_plan(
        logits, rm, exact_cap=ec, invoke_cap=ic,
        backend=a.backend, block_t=a.block_t,
        tier=tr, tier_margins=tier_margins, residency=residency)


def approx_ffn_serve(cfg: ModelConfig, p, x: jax.Array,
                     row_mask: jax.Array | None = None, plan=None,
                     tier: jax.Array | None = None,
                     tier_margins: jax.Array | None = None,
                     residency: jax.Array | None = None):
    """Serving path with capacity dispatch.  x: (B, S, d) -> (out, aux).

    Exact FFN runs on ``exact_frac``·T tokens only — the paper's invocation
    gain realized as a FLOP reduction.  invoke capacity per approximator is
    sized for a balanced dispatch with slack (or per class via
    ``approx.invoke_fracs``).

    ``row_mask`` (optional, (B,) bool) marks the ACTIVE batch rows — a
    decode server's occupied slots.  Idle rows are excluded from dispatch
    and from every invoke stat, so invocation/exact_frac (and any capacity
    autotuner reading them) stay exact on partially-full slot tables.

    ``tier`` (optional, (B,) int32) + ``tier_margins`` ((n_tiers,)
    traced): per-request QoS — each slot routes at its own error-bound
    tier via the exact-logit margin (runtime/dispatch.route) and the
    invoke stats gain the per-tier split.

    ``plan`` (optional, a runtime/dispatch.DispatchPlan): tick-scope
    routing — the decision was made ONCE above the layer scan
    (make_tick_plan) and this layer only executes against it; no router
    matmul, sort, or stats collective runs here, and ``row_mask``/
    ``tier`` are ignored (the plan already embeds them).

    ``residency`` (optional, (n_resident,) int32 library ids, TRACED):
    approximator-library serving — the stored stacks hold the full
    library and the router head is library-wide; the resident rows are
    gathered out per layer (ops.gather_resident_stacks — an
    (n_resident + 1)-row gather, tiny) and library routing folds onto the
    resident slots (runtime/dispatch).  A hot-set swap is a new vector
    through the same compiled program.  With a tick ``plan`` the fold
    already happened in make_tick_plan (pass the SAME residency there);
    here it only selects the executed weights.

    The engine is ``runtime/dispatch.mcma_dispatch`` (classify -> capacity
    -> class-sort -> weight-switch kernel / XLA oracle -> exact -> scatter);
    ``cfg.approx.backend`` picks the backend.  Under a distributed mesh the
    shard_map path below runs the SAME engine per data shard with
    psum-reduced invoke_stats (global cumsum ranking across a
    token-sharded dim would force the partitioner to replicate tokens, so
    each data shard ranks/gathers only its own tokens — §Perf B/C).
    """
    from repro.runtime.dispatch import (execute_dispatch, mcma_dispatch,
                                        plan_invoke_stats)
    a = cfg.approx
    b, s, d = x.shape
    t = b * s
    mesh, dp, _ = _manual_serve_ctx(cfg, b)
    if mesh is not None:
        return _approx_serve_manual(cfg, p, x, mesh, dp,
                                    row_mask=row_mask, plan=plan,
                                    tier=tier, tier_margins=tier_margins,
                                    residency=residency)

    if plan is not None:
        stacks = (p["a_w1"], p["a_b1"], p["a_w2"], p["a_b2"])
        if residency is not None:
            from repro.kernels.ops import gather_resident_stacks
            stacks = gather_resident_stacks(*stacks,
                                            residency.astype(jnp.int32))
        out = execute_dispatch(
            plan, x.reshape(t, d), lambda xb: ffn_fwd(cfg, p["ffn"], xb),
            *stacks, interpret=a.interpret, weights_prepadded=True)
        stats = plan_invoke_stats(plan)
    else:
        xt = x.reshape(t, d)
        rm = _row_mask_tokens(row_mask, s)
        tr, tier_margins = _tier_args(cfg, tier, tier_margins, s)
        ec, ic = serve_caps(cfg, t)
        logits = jnp.dot(xt, p["router"].astype(x.dtype)).astype(jnp.float32)
        out, stats = mcma_dispatch(
            xt, logits, lambda xb: ffn_fwd(cfg, p["ffn"], xb),
            p["a_w1"], p["a_b1"], p["a_w2"], p["a_b2"],
            exact_cap=ec, invoke_cap=ic,
            backend=a.backend, block_t=a.block_t, interpret=a.interpret,
            row_mask=rm, weights_prepadded=True,
            tier=tr, tier_margins=tier_margins, residency=residency)

    aux = {"loss": jnp.zeros((), jnp.float32),
           "invocation": stats["invocation"],
           "router_acc": jnp.zeros((), jnp.float32),
           "invoke_stats": stats}
    return out.reshape(b, s, d), aux


def _approx_serve_manual(cfg: ModelConfig, p, x, mesh, dp, row_mask=None,
                         plan=None, tier=None, tier_margins=None,
                         residency=None):
    """Shard_map-native serve dispatch: the SAME ``mcma_dispatch`` engine
    as the single-device path, run per data shard (each shard classifies /
    capacities / class-sorts / weight-switches its OWN tokens — no
    cross-shard dispatch traffic, same lesson as the manual MoE path,
    §Perf B/C).  The exact FFN runs Megatron-TP over "model" with one psum
    inside the engine's capacity gather; the approximators are replicated
    (tiny) and run locally; invoke_stats are psum-reduced over the data
    axes so every shard reports the global totals.  Per-shard capacities
    come from sharding/rules.shard_capacity (``cfg.approx.shard_slack``
    over-provisions them against cross-shard class skew).

    ``plan`` (tick scope): the DispatchPlan was built per data shard by
    ``make_tick_plan`` under the SAME row sharding this region re-enters,
    so each shard executes its local rows against its local plan fields;
    the plan's count fields are already psum-reduced global totals, so
    the stats come straight off the plan with no collective here.

    ``tier``/``tier_margins`` (layer scope only — a tick plan already
    embeds the tiers): the (B,) per-slot QoS tiers ride through the
    shard_map batch-sharded like the mask, the margins replicated, and
    the per-tier stats psum-reduce with the rest.

    ``residency`` (library serving): the stacks in ``weights`` hold the
    full replicated library; on the plan path the resident rows are
    gathered OUTSIDE the shard_map (the gathered stacks are replicated
    with the same specs, just a smaller leading dim — specs are
    shape-agnostic), on the layer-scope path the replicated residency
    vector rides in and ``mcma_dispatch`` gathers per shard.
    """
    from repro.runtime.dispatch import (execute_dispatch, mcma_dispatch,
                                        plan_invoke_stats)
    from repro.sharding.rules import approx_serve_specs
    a = cfg.approx
    b, s, d = x.shape
    axes = tuple(dp) + ("model",)
    weights = {**{k: p[k] for k in ("router", "a_w1", "a_b1", "a_w2",
                                    "a_b2")}, "ffn": p["ffn"]}
    if residency is not None and plan is not None:
        from repro.kernels.ops import gather_resident_stacks
        weights["a_w1"], weights["a_b1"], weights["a_w2"], weights["a_b2"] \
            = gather_resident_stacks(
                weights["a_w1"], weights["a_b1"], weights["a_w2"],
                weights["a_b2"], residency.astype(jnp.int32))

    def tp_exact_fn(p_loc):
        # FSDP unshard-on-use of the exact FFN's TP slices
        w_in = jax.lax.all_gather(p_loc["ffn"]["w_in"], dp, axis=0, tiled=True)
        w_out = jax.lax.all_gather(p_loc["ffn"]["w_out"], dp, axis=1, tiled=True)
        w_gate = (jax.lax.all_gather(p_loc["ffn"]["w_gate"], dp, axis=0,
                                     tiled=True)
                  if "w_gate" in p_loc["ffn"] else None)

        def exact_fn(xb):
            # Megatron-TP: d_ff sharded over "model", one psum per call
            h = jnp.dot(xb, w_in.astype(xb.dtype))
            if w_gate is not None:
                h = jax.nn.silu(jnp.dot(xb, w_gate.astype(xb.dtype))) * h
            else:
                h = jax.nn.silu(h)
            return jax.lax.psum(jnp.dot(h, w_out.astype(h.dtype)), "model")
        return exact_fn

    if plan is not None:
        specs = approx_serve_specs(mesh, gated="w_gate" in p["ffn"],
                                   plan=plan)

        def local_plan(p_loc, x_loc, plan_loc):
            bl, sl, _ = x_loc.shape
            xt = x_loc.reshape(bl * sl, d)
            out = execute_dispatch(
                plan_loc, xt, tp_exact_fn(p_loc),
                p_loc["a_w1"], p_loc["a_b1"], p_loc["a_w2"], p_loc["a_b2"],
                interpret=a.interpret, weights_prepadded=True)
            return out.reshape(bl, sl, d)

        fn = jax.shard_map(local_plan, mesh=mesh, in_specs=specs["in"],
                           out_specs=specs["out"],
                           axis_names=frozenset(axes), check_vma=False)
        out = fn(weights, x, plan)
        stats = plan_invoke_stats(plan)
    else:
        has_tier = tier is not None
        has_res = residency is not None
        if row_mask is None:
            row_mask = jnp.ones((b,), bool)
        specs = approx_serve_specs(mesh, gated="w_gate" in p["ffn"],
                                   with_tier=has_tier,
                                   mask2d=row_mask.ndim == 2,
                                   with_residency=has_res)
        if has_tier and tier_margins is None:
            tier_margins = _default_margins(cfg)

        def local(p_loc, x_loc, m_loc, *extra):
            extra = list(extra)
            t_l, tm = (extra.pop(0), extra.pop(0)) if has_tier \
                else (None, None)
            res = extra.pop(0) if has_res else None
            bl, sl, _ = x_loc.shape
            tl = bl * sl
            xt = x_loc.reshape(tl, d)
            rm = _row_mask_tokens(m_loc, sl)
            ec, ic = serve_caps(cfg, tl)
            logits = jnp.dot(xt, p_loc["router"].astype(xt.dtype)) \
                .astype(jnp.float32)
            out, stats = mcma_dispatch(
                xt, logits, tp_exact_fn(p_loc),
                p_loc["a_w1"], p_loc["a_b1"], p_loc["a_w2"], p_loc["a_b2"],
                exact_cap=ec, invoke_cap=ic,
                backend=a.backend, block_t=a.block_t, interpret=a.interpret,
                stats_axes=dp, row_mask=rm, weights_prepadded=True,
                tier=None if t_l is None else jnp.repeat(t_l, sl),
                tier_margins=tm, residency=res)
            return out.reshape(bl, sl, d), stats

        fn = jax.shard_map(local, mesh=mesh, in_specs=specs["in"],
                           out_specs=specs["out"],
                           axis_names=frozenset(axes), check_vma=False)
        args = (weights, x, row_mask)
        if has_tier:
            args = args + (tier.astype(jnp.int32), tier_margins)
        if has_res:
            args = args + (residency.astype(jnp.int32),)
        out, stats = fn(*args)
    aux = {"loss": jnp.zeros((), jnp.float32),
           "invocation": stats["invocation"],
           "router_acc": jnp.zeros((), jnp.float32),
           "invoke_stats": stats}
    return out, aux


def approx_ffn_fwd(cfg: ModelConfig, p, x: jax.Array, *, serve: bool = False,
                   row_mask: jax.Array | None = None, plan=None,
                   tier: jax.Array | None = None,
                   tier_margins: jax.Array | None = None,
                   residency: jax.Array | None = None):
    if serve:
        return approx_ffn_serve(cfg, p, x, row_mask=row_mask, plan=plan,
                                tier=tier, tier_margins=tier_margins,
                                residency=residency)
    return approx_ffn_train(cfg, p, x)
