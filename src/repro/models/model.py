"""Model assembly: every assigned architecture family as one functional LM.

Families and their block layouts:
  dense / audio / vlm : N x (attn + FFN)            [scan over layers]
  moe                 : N x (attn + MoE-FFN)        [scan over layers]
  ssm (xLSTM)         : G x ((k-1) mLSTM + 1 sLSTM) [scan over groups, inner
                        scan over the mLSTM run]    (k = ssm.slstm_every)
  hybrid (zamba2)     : G x (k mamba2 + SHARED attn/FFN block)  (k =
                        attn_every; the shared block's params are one set
                        applied at every group boundary — Zamba2's design)

All stacking uses lax.scan over stacked param pytrees so compile time is
O(1) in depth; ``cfg.remat`` wraps block bodies in jax.checkpoint.

Steps (the units the dry-run lowers):
  train_step   (state, batch)  -> (state, metrics)  — fwd + bwd + optimizer
  prefill_step (params, batch) -> (last_logits, cache)
  decode_step  (params, cache, tokens) -> (logits, cache)

Inputs are tokens (B, S) int32, or precomputed embeddings (B, S, d) for
``input_mode="embeddings"`` (audio/vlm stubs).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import held_lengths
from repro.models import layers as L
from repro.models import mamba2, moe, xlstm
from repro.models.approx_ffn import approx_ffn_fwd, init_approx_ffn
from repro.sharding.activations import constrain


# ---------------------------------------------------------------------------
# Per-family block init/apply
# ---------------------------------------------------------------------------

def _init_dense_block(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": L.init_norm(cfg, cfg.d_model), "attn": L.init_attn(k1, cfg),
         "ln2": L.init_norm(cfg, cfg.d_model)}
    if cfg.moe.n_experts:
        p["moe"] = moe.init_moe(k2, cfg)
    elif cfg.approx.enable:
        p["approx"] = init_approx_ffn(k3, cfg)
    else:
        p["ffn"] = L.init_ffn(k4, cfg)
    return p


def _dense_block(cfg: ModelConfig, p, x, positions, cache, *, serve=False,
                 row_mask=None, dispatch_plan=None, tier=None,
                 tier_margins=None, residency=None):
    """One transformer block.  Returns (x, new_cache, aux_loss, aux_metrics).

    ``dispatch_plan`` (serve + route_scope="tick"): the per-tick
    DispatchPlan built above the layer scan — this block's ApproxFFN
    executes against it instead of routing its own tokens.  ``tier``/
    ``tier_margins`` (serve, layer scope): per-slot QoS tiers for this
    block's own routing decision (a tick plan already embeds them).
    ``residency`` (serve, library): the traced (n_resident,) library
    residency map selecting the executed approximator rows."""
    h, new_cache = L.attention_fwd(cfg, p["attn"], L.norm_fwd(cfg, p["ln1"], x),
                                   positions, cache)
    aux = jnp.zeros((), jnp.float32)
    metrics = {}
    if cfg.parallel_block:
        # stablelm-2 style: FFN in parallel with attention, one residual
        f = _ffn_part(cfg, p, L.norm_fwd(cfg, p["ln1"], x), serve, row_mask,
                      dispatch_plan, tier, tier_margins, residency)
        f, aux, metrics = f
        x = x + h + f
    else:
        x = x + h
        f, aux, metrics = _ffn_part(cfg, p, L.norm_fwd(cfg, p["ln2"], x),
                                    serve, row_mask, dispatch_plan, tier,
                                    tier_margins, residency)
        x = x + f
    return x, new_cache, aux, metrics


def _ffn_part(cfg: ModelConfig, p, xn, serve, row_mask=None,
              dispatch_plan=None, tier=None, tier_margins=None,
              residency=None):
    if cfg.moe.n_experts:
        y, aux = moe.moe_fwd(cfg, p["moe"], xn)
        return y, aux, {}
    if cfg.approx.enable:
        y, a = approx_ffn_fwd(cfg, p["approx"], xn, serve=serve,
                              row_mask=row_mask, plan=dispatch_plan,
                              tier=tier, tier_margins=tier_margins,
                              residency=residency)
        m = {"invocation": a["invocation"], "router_acc": a["router_acc"]}
        if "label_votes" in a:  # train path: per-token competitive labels,
            # summed over the layer scan to supervise the tick-router head
            m["_label_votes"] = a["label_votes"]
        st = a.get("invoke_stats")
        if st is not None:  # serve-mode dispatch engine reports these
            total = jnp.maximum(jnp.sum(st["class_counts"]), 1) \
                .astype(jnp.float32)
            m["exact_frac"] = st["exact_frac"]
            m["dropped_frac"] = st["dropped"].astype(jnp.float32) / total
            m["padding_rows"] = st["padding_rows"].astype(jnp.float32)
            # the capacity autotuner's raw signal (global under a mesh):
            # routed + post-capacity per-class counts, dropped rows
            m["class_counts"] = st["class_counts"].astype(jnp.float32)
            m["dispatched"] = st["dispatched"].astype(jnp.float32)
            m["dropped_rows"] = st["dropped"].astype(jnp.float32)
            # per-tier QoS split: (n_tiers, n+1) routed / post-capacity
            # counts — the server attributes served invocation and drops
            # to each request's error-bound tier from these
            m["tier_counts"] = st["tier_counts"].astype(jnp.float32)
            m["tier_dispatched"] = st["tier_dispatched"] \
                .astype(jnp.float32)
            # library residency: full-library demand (the
            # ResidencyController's promotion signal) + off-set rows
            # folded onto the exact path (lib_counts == class_counts and
            # 0 off-set rows on library-less deployments)
            m["lib_counts"] = st["lib_counts"].astype(jnp.float32)
            m["off_set_exact_rows"] = st["off_set_exact_rows"] \
                .astype(jnp.float32)
        return y, a["loss"], m
    return L.ffn_fwd(cfg, p["ffn"], xn), jnp.zeros((), jnp.float32), {}


# ---- xLSTM ---------------------------------------------------------------

def _init_mlstm_block(key, cfg):
    return {"ln": L.init_norm(cfg, cfg.d_model), "core": xlstm.init_mlstm(key, cfg)}


def _init_slstm_block(key, cfg):
    return {"ln": L.init_norm(cfg, cfg.d_model), "core": xlstm.init_slstm(key, cfg)}


def _mlstm_block(cfg, p, x, state):
    y, st = xlstm.mlstm_fwd(cfg, p["core"], L.norm_fwd(cfg, p["ln"], x), state)
    return x + y, st


def _slstm_block(cfg, p, x, state):
    y, st = xlstm.slstm_fwd(cfg, p["core"], L.norm_fwd(cfg, p["ln"], x), state)
    return x + y, st


# ---- zamba2 hybrid ---------------------------------------------------------

def _init_mamba_block(key, cfg):
    return {"ln": L.init_norm(cfg, cfg.d_model), "core": mamba2.init_mamba(key, cfg)}


def _mamba_block(cfg, p, x, state):
    y, st = mamba2.mamba_fwd(cfg, p["core"], L.norm_fwd(cfg, p["ln"], x), state)
    return x + y, st


# ---------------------------------------------------------------------------
# Model topology descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """How layers group into scan stacks for a family."""

    kind: str            # "uniform" | "xlstm" | "hybrid"
    n_groups: int = 0
    per_group: int = 0   # inner homogeneous run length


def topology(cfg: ModelConfig) -> Topology:
    if cfg.family == "ssm":
        k = cfg.ssm.slstm_every
        assert cfg.n_layers % k == 0, (cfg.n_layers, k)
        return Topology("xlstm", cfg.n_layers // k, k - 1)
    if cfg.family == "hybrid":
        k = cfg.attn_every or 6
        assert cfg.n_layers % k == 0, (cfg.n_layers, k)
        return Topology("hybrid", cfg.n_layers // k, k)
    return Topology("uniform", cfg.n_layers, 1)


def _stack_init(key, n, init_fn):
    """Init n copies of a block with stacked leaves (leading dim n)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_model(key: jax.Array, cfg: ModelConfig):
    topo = topology(cfg)
    ke, kb, ks = jax.random.split(key, 3)
    params: dict[str, Any] = {"embed": L.init_embed(ke, cfg),
                              "ln_f": L.init_norm(cfg, cfg.d_model)}
    if topo.kind == "uniform":
        params["blocks"] = _stack_init(kb, topo.n_groups,
                                       lambda k: _init_dense_block(k, cfg))
    elif topo.kind == "xlstm":
        km, ksl = jax.random.split(kb)
        params["mlstm"] = _stack_init(
            km, topo.n_groups * topo.per_group,
            lambda k: _init_mlstm_block(k, cfg))
        params["mlstm"] = jax.tree.map(
            lambda a: a.reshape(topo.n_groups, topo.per_group, *a.shape[1:]),
            params["mlstm"])
        params["slstm"] = _stack_init(ksl, topo.n_groups,
                                      lambda k: _init_slstm_block(k, cfg))
    else:  # hybrid
        km, ka = jax.random.split(kb)
        params["mamba"] = _stack_init(
            km, topo.n_groups * topo.per_group,
            lambda k: _init_mamba_block(k, cfg))
        params["mamba"] = jax.tree.map(
            lambda a: a.reshape(topo.n_groups, topo.per_group, *a.shape[1:]),
            params["mamba"])
        # ONE shared attention+FFN block (Zamba2), applied per group
        params["shared"] = _init_dense_block(ks, dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=0)))
    if cfg.approx.enable and not cfg.moe.n_experts \
            and topo.kind in ("uniform", "hybrid"):
        # tick-router head (route_scope="tick"): ONE (d, n+1) classifier on
        # the pre-layer hidden state, co-trained on the competitive labels
        # aggregated across layers — the paper's one decision per input,
        # made once per decode tick and reused by every layer of the scan
        params["tick_router"] = jax.random.normal(
            jax.random.fold_in(ke, 1),
            (cfg.d_model, cfg.approx.n_live + 1),
            cfg.pdtype) * cfg.d_model ** -0.5
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill): scan over stacked blocks
# ---------------------------------------------------------------------------

def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(cfg: ModelConfig, params, inputs: jax.Array, *,
            collect_cache: bool = False, serve: bool = False):
    """Full-sequence forward.  inputs: tokens (B, S) or embeds (B, S, d).

    Returns (logits (B, S, V), cache-or-None, aux_loss, metrics).
    """
    topo = topology(cfg)
    x = constrain(L.embed_fwd(cfg, params["embed"], inputs))
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)[None, :]
    aux_total = jnp.zeros((), jnp.float32)
    metrics: dict[str, jax.Array] = {}
    cache = None
    # tick-router co-training: accumulate every layer's competitive labels
    # (one-hot votes) through the scan carry; after the scan the TICK
    # router head trains on the across-layer modal label per token
    train_tick = ("tick_router" in params and not serve
                  and cfg.approx.enable and not cfg.moe.n_experts)
    x0 = x
    votes0 = jnp.zeros((b * s, cfg.approx.n_live + 1), jnp.float32)

    if topo.kind == "uniform":
        def body(carry, blk):
            x, votes = carry
            x, kv, aux, m = _dense_block(cfg, blk, x, positions, None, serve=serve)
            if "_label_votes" in m:
                votes = votes + m.pop("_label_votes")
            # K/V are scan outputs ONLY when prefill needs them — XLA does
            # not reliably DCE unused (L, B, S, KV, hd) while-loop outputs
            kvs = (kv["k"], kv["v"]) if collect_cache else ()
            return (constrain(x), votes), (aux, m, kvs)
        (x, votes), (auxs, ms, kvs) = jax.lax.scan(
            _maybe_remat(cfg, body), (x, votes0), params["blocks"])
        aux_total = jnp.sum(auxs)
        # layer mean over the scan axis only: scalar metrics stay scalar,
        # per-class vectors (class_counts/dispatched) stay (n+1,)
        metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}
        if collect_cache:
            ks, vs = kvs
            if cfg.sliding_window:
                w = min(s, cfg.sliding_window)
                assert s % w == 0, "ring-buffer alignment needs S % window == 0"
                ks, vs = ks[:, :, -w:], vs[:, :, -w:]
            cache = {"k": ks, "v": vs, "pos": jnp.full((b,), s, jnp.int32)}

    elif topo.kind == "xlstm":
        def group(x, grp):
            mblks, sblk = grp

            def inner(x, blk):
                x, st = _mlstm_block(cfg, blk, x, None)
                return constrain(x), st
            x, msts = jax.lax.scan(_maybe_remat(cfg, inner), x, mblks)
            x, sst = _slstm_block(cfg, sblk, x, None)
            return constrain(x), (msts, sst)
        x, (mstates, sstates) = jax.lax.scan(
            _maybe_remat(cfg, group), x, (params["mlstm"], params["slstm"]))
        if collect_cache:
            cache = {"mlstm": mstates, "slstm": sstates,
                     "pos": jnp.full((b,), s, jnp.int32)}

    else:  # hybrid
        shared = params["shared"]

        def group(carry, mblks):
            x, votes = carry

            def inner(x, blk):
                x, st = _mamba_block(cfg, blk, x, None)
                return constrain(x), st
            x, msts = jax.lax.scan(_maybe_remat(cfg, inner), x, mblks)
            x, kv, aux, m = _dense_block(cfg, shared, x, positions, None,
                                         serve=serve)
            if "_label_votes" in m:
                votes = votes + m.pop("_label_votes")
            kvs = (kv["k"], kv["v"]) if collect_cache else ()
            return (constrain(x), votes), (msts, aux, m, kvs)
        (x, votes), (mstates, auxs, ms, kvs) = jax.lax.scan(
            _maybe_remat(cfg, group), (x, votes0), params["mamba"])
        aux_total = jnp.sum(auxs)
        # group mean of the shared block's metrics (the uniform-family
        # convention) — the dispatch/invocation signal was previously
        # dropped here, leaving the autotuner blind for this family
        metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}
        if collect_cache:
            ks, vs = kvs
            cache = {"mamba": mstates, "k": ks, "v": vs,
                     "pos": jnp.full((b,), s, jnp.int32)}

    if train_tick and topo.kind in ("uniform", "hybrid"):
        tick_labels = jnp.argmax(votes, -1)
        t_logits = jnp.dot(x0.reshape(b * s, -1),
                           params["tick_router"].astype(x0.dtype)) \
            .astype(jnp.float32)
        logp = jax.nn.log_softmax(t_logits, -1)
        tick_loss = -jnp.mean(jnp.take_along_axis(logp,
                                                  tick_labels[:, None], 1))
        aux_total = aux_total + cfg.approx.router_weight * tick_loss
        metrics = dict(metrics, tick_router_loss=tick_loss,
                       tick_router_acc=jnp.mean(
                           (jnp.argmax(t_logits, -1) == tick_labels)
                           .astype(jnp.float32)))

    x = L.norm_fwd(cfg, params["ln_f"], x)
    logits = L.unembed_fwd(cfg, params["embed"], x)
    return logits, cache, aux_total, metrics


# ---------------------------------------------------------------------------
# Decode (single token, cache update)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               page_size: int = 0, kv_pages: int = 0):
    """Empty decode cache sized for ``max_len`` context.

    ``page_size > 0`` builds the PAGED layout (uniform family only): k/v
    are per-layer pools ``(L, kv_pages, page_size, Kh, hd)`` plus ONE
    ``block_table`` (batch, max_len // page_size) shared by every layer —
    all layers write the same positions, so one table serves the stack.
    ``page_size=0`` keeps the dense ``(L, batch, max_len, Kh, hd)``
    layout, the bit-exact oracle."""
    topo = topology(cfg)
    if page_size:
        assert topo.kind == "uniform" and not cfg.sliding_window, (
            "paged KV caches need the uniform dense-attention family "
            f"(got family={cfg.family!r}, "
            f"sliding_window={cfg.sliding_window})")
        c = L.init_attn_cache(cfg, batch, max_len, page_size=page_size,
                              n_pages=kv_pages)
        stack = lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape))
        return {"k": stack(c["k"]), "v": stack(c["v"]),
                "block_table": c["block_table"],
                "pos": jnp.zeros((batch,), jnp.int32)}
    if topo.kind == "uniform":
        c = L.init_attn_cache(cfg, batch, max_len)
        stack = lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape))
        return {"k": stack(c["k"]), "v": stack(c["v"]),
                "pos": jnp.zeros((batch,), jnp.int32)}
    if topo.kind == "xlstm":
        m = xlstm.init_mlstm_state(cfg, batch)
        sl = xlstm.init_slstm_state(cfg, batch)
        st = lambda a, n: jnp.broadcast_to(a, n + a.shape)
        return {"mlstm": jax.tree.map(
                    lambda a: st(a, (topo.n_groups, topo.per_group)), m),
                "slstm": jax.tree.map(lambda a: st(a, (topo.n_groups,)), sl),
                "pos": jnp.zeros((batch,), jnp.int32)}
    # hybrid
    ms = mamba2.init_mamba_state(cfg, batch)
    ac = L.init_attn_cache(cfg, batch, max_len)
    st = lambda a, n: jnp.broadcast_to(a, n + a.shape)
    return {"mamba": jax.tree.map(
                lambda a: st(a, (topo.n_groups, topo.per_group)), ms),
            "k": st(ac["k"], (topo.n_groups,)), "v": st(ac["v"], (topo.n_groups,)),
            "pos": jnp.zeros((batch,), jnp.int32)}


def reset_slot(cfg: ModelConfig, cache, fresh, slot: int):
    """Reset one batch slot of a decode cache to ``fresh`` (a cache from
    init_cache): continuous batching admits a new request into a freed
    slot.  Batch-dim position per leaf: k/v (L, B, ...) -> 1; mlstm/mamba
    states (G, P, B, ...) -> 2; slstm states (G, B, ...) -> 1; pos -> 0.

    Paged caches (``block_table`` present): the k/v pools are SHARED by
    every slot — freeing pages is the server allocator's job, so the
    pools pass through untouched and only the slot's block-table row
    (back to -1) and ``pos`` (back to 0) reset."""
    paged = isinstance(cache, dict) and "block_table" in cache

    def bdim(path):
        head = path[0]
        if head in ("k", "v"):
            return None if paged else 1
        if head in ("mlstm", "mamba"):
            return 2
        if head == "slstm":
            return 1
        return 0  # pos, block_table

    def walk(path, c, f):
        if isinstance(c, dict):
            return {k: walk(path + (k,), c[k], f[k]) for k in c}
        d = bdim(path)
        if d is None:
            return c                       # shared page pool: not per-slot
        idx = tuple([slice(None)] * d + [slot])
        return c.at[idx].set(f[idx])
    return walk((), cache, fresh)


def pad_cache(cfg: ModelConfig, cache, max_len: int):
    """Grow a prefill-built cache's KV length to ``max_len`` (decode room).
    No-op for pure-SSM caches, ring buffers (fixed window), and paged
    caches (a fixed pool — capacity is kv_pages, not per-slot length)."""
    if "k" not in cache or cfg.sliding_window or "block_table" in cache:
        return cache
    pad = max_len - cache["k"].shape[2]
    if pad <= 0:
        return cache
    grow = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return dict(cache, k=grow(cache["k"]), v=grow(cache["v"]))


def _pages_read_in_place(cfg: ModelConfig) -> bool:
    """Whether a paged decode step attends with the paged kernel
    (kernels/paged_attention.py) on the layer stack: under a Pallas
    dispatch backend, off a mesh.  The ``xla`` backend (the oracle) keeps
    the gathered path, bit-identical to a dense cache; so does a mesh
    server, where a ``pallas_call`` left to the partitioner would gather
    the replicated pool onto every device."""
    from repro.runtime.dispatch import PALLAS_BACKENDS
    from repro.sharding.activations import manual_dp_context
    return (cfg.approx.backend in PALLAS_BACKENDS
            and manual_dp_context()[0] is None)


def decode(cfg: ModelConfig, params, cache, inputs: jax.Array, *,
           serve: bool = True, collect_metrics: bool = False,
           row_mask: jax.Array | None = None,
           tier: jax.Array | None = None,
           tier_margins: jax.Array | None = None,
           residency: jax.Array | None = None):
    """One decode step.  inputs: tokens (B, 1) or embeds (B, 1, d).
    Returns (logits (B, V), new_cache), or (logits, new_cache, metrics)
    when ``collect_metrics`` — layer-meaned per-step block metrics (e.g.
    the ApproxFFN dispatch invocation rate; uniform family only).

    ``row_mask`` (optional, (B,) bool) marks the ACTIVE batch slots of a
    continuous-batching server.  Idle slots (fed dummy token 0) are
    excluded from the serve-mode dispatch and its invoke stats, so the
    reported invocation/exact_frac are exact on partially-full tables.

    ``tier`` (optional, (B,) int32) + ``tier_margins`` ((n_tiers,)
    float32, TRACED — margin changes never retrace): per-slot QoS tiers.
    Each slot's row routes at its own error-bound tier via the
    exact-logit margin (runtime/dispatch.route), one batch mixing tiers
    freely, and the metrics gain the per-tier invoke-stat split.  With
    ``tier=None`` the step traces the margin-free program unchanged.

    ``cfg.approx.route_scope="tick"``: the MCMA routing decision is made
    ONCE per tick — a DispatchPlan built from the tick-router head on the
    pre-layer hidden state (approx_ffn.make_tick_plan), hoisted above the
    layer scan and reused by every layer, so each layer's dispatch is one
    weight-switch launch on already-sorted rows (no per-layer argsort/
    bincount/rank), and the reported invoke stats are the ONE tick-level
    observation (every layer sees the same plan).

    ``residency`` (optional, (n_resident,) int32 library ids, TRACED):
    approximator-library serving (``cfg.approx.library_size > 0``) —
    routing covers the full library, the residency map folds classes onto
    resident slots, and every layer executes against the residency-
    gathered weight rows.  A hot-set swap between ticks is a new vector
    through this same compiled step — zero retraces.

    A paged cache under a Pallas dispatch backend, off a mesh
    (``_pages_read_in_place``): every layer scatters its K/V into the
    carried ``(L, N, P, Kh, hd)`` stack and the paged attention kernel
    reads each slot's held pages there, up to ``pos`` (a masked slot
    reads none); no layer pool is sliced out or written back."""
    topo = topology(cfg)
    x = L.embed_fwd(cfg, params["embed"], inputs)
    pos = cache["pos"]                                   # (B,) per-slot
    positions = pos[:, None]
    step_metrics: dict[str, jax.Array] = {}
    plan = None
    if serve and cfg.approx.enable:
        if cfg.approx.route_scope not in ("layer", "tick"):
            # a typo would otherwise silently fall back to per-layer routing
            raise ValueError(f"unknown route_scope: "
                             f"{cfg.approx.route_scope!r} "
                             "(expected 'layer' or 'tick')")
        if (cfg.approx.route_scope == "tick" and not cfg.moe.n_experts
                and topo.kind in ("uniform", "hybrid")):
            from repro.models.approx_ffn import make_tick_plan
            plan = make_tick_plan(cfg, params, x, row_mask, tier=tier,
                                  tier_margins=tier_margins,
                                  residency=residency)
            tier = tier_margins = None   # the plan embeds the tiers

    if topo.kind == "uniform":
        # The cache is CARRIED and updated in place (dynamic-update-slice
        # inside the while loop aliases the donated input buffer) — passing
        # it as scan xs/ys would materialize two extra (L, B, S, KV, hd)
        # temporaries, which at 32k context is the whole HBM budget.
        bt = cache.get("block_table")     # paged: ONE table for all layers
        in_place = bt is not None and _pages_read_in_place(cfg)
        if in_place:
            # tokens each slot attends; a masked slot fetches nothing
            lengths = held_lengths(pos, bt, cache["k"].shape[2])
            if row_mask is not None:
                lengths = jnp.where(row_mask, lengths, 0)

        def body(carry, blk_i):
            x, ck, cv = carry
            blk, i = blk_i
            if in_place:
                lc = {"k": ck, "v": cv, "pos": pos, "block_table": bt,
                      "layer": i, "lengths": lengths}
            else:
                with jax.named_scope(scopes.KV_GATHER):
                    lc = {"k": ck[i], "v": cv[i], "pos": pos}
                if bt is not None:
                    lc["block_table"] = bt
            x, nc, _, m = _dense_block(cfg, blk, x, positions, lc, serve=serve,
                                       row_mask=row_mask, dispatch_plan=plan,
                                       tier=tier, tier_margins=tier_margins,
                                       residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            if in_place:
                ck, cv = nc["k"], nc["v"]
            else:
                with jax.named_scope(scopes.KV_WRITE):
                    ck = jax.lax.dynamic_update_index_in_dim(ck, nc["k"], i, 0)
                    cv = jax.lax.dynamic_update_index_in_dim(cv, nc["v"], i, 0)
            return (x, ck, cv), (m if collect_metrics else None)
        (x, ks, vs), ms = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        # ``row_mask`` also gates the position advance: a masked slot HOLDS
        # its sequence offset (its dummy-token KV write at ``pos`` is
        # overwritten by the slot's next real token), so a chunked-prefill
        # scheduler can run decode ticks while other slots sit mid-prompt
        # without corrupting them.  Unmasked rows see pos + 1 exactly.
        adv = 1 if row_mask is None else row_mask.astype(jnp.int32)
        new_cache = {"k": ks, "v": vs, "pos": pos + adv}
        if bt is not None:
            new_cache["block_table"] = bt
        if collect_metrics and ms is not None:
            step_metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}

    elif topo.kind == "xlstm":
        def group(x, grp):
            mblks, msts, sblk, sst = grp

            def inner(x, bs):
                blk, st = bs
                x, ns = _mlstm_block(cfg, blk, x, st)
                return x, ns
            x, nmsts = jax.lax.scan(inner, x, (mblks, msts))
            x, nsst = _slstm_block(cfg, sblk, x, sst)
            return x, (nmsts, nsst)
        x, (nm, nsl) = jax.lax.scan(
            group, x, (params["mlstm"], cache["mlstm"], params["slstm"],
                       cache["slstm"]))
        new_cache = {"mlstm": nm, "slstm": nsl, "pos": pos + 1}

    else:  # hybrid
        shared = params["shared"]
        topo_g = topo.n_groups

        def group(carry, grp):
            x, ck, cv = carry
            mblks, msts, gi = grp

            def inner(x, bs):
                blk, st = bs
                x, ns = _mamba_block(cfg, blk, x, st)
                return x, ns
            x, nmsts = jax.lax.scan(inner, x, (mblks, msts))
            with jax.named_scope(scopes.KV_GATHER):
                lc = {"k": ck[gi], "v": cv[gi], "pos": pos}
            x, nc, _, m = _dense_block(cfg, shared, x, positions, lc,
                                       serve=serve, row_mask=row_mask,
                                       dispatch_plan=plan, tier=tier,
                                       tier_margins=tier_margins,
                                       residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            with jax.named_scope(scopes.KV_WRITE):
                ck = jax.lax.dynamic_update_index_in_dim(ck, nc["k"], gi, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, nc["v"], gi, 0)
            return (x, ck, cv), (nmsts, m if collect_metrics else None)
        (x, ks, vs), (nm, ms) = jax.lax.scan(
            group, (x, cache["k"], cache["v"]),
            (params["mamba"], cache["mamba"], jnp.arange(topo_g)))
        new_cache = {"mamba": nm, "k": ks, "v": vs, "pos": pos + 1}
        if collect_metrics and ms is not None:
            # group mean of the shared block's dispatch metrics — this used
            # to be dropped, leaving the autotuner blind for this family
            step_metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}

    x = L.norm_fwd(cfg, params["ln_f"], x)
    logits = L.unembed_fwd(cfg, params["embed"], x)
    if collect_metrics:
        return logits[:, 0], new_cache, step_metrics
    return logits[:, 0], new_cache


def decode_chunk(cfg: ModelConfig, params, cache, tokens: jax.Array,
                 n_valid: jax.Array, *, serve: bool = True,
                 collect_metrics: bool = False,
                 row_mask: jax.Array | None = None,
                 tier: jax.Array | None = None,
                 tier_margins: jax.Array | None = None,
                 residency: jax.Array | None = None):
    """One chunked-PREFILL step against the decode cache layout.

    tokens: (B, S) int32 — up to S prompt tokens per slot, appended to each
    slot's cache at its own offset ``cache["pos"]``; ``n_valid`` (B,) int32
    counts the real tokens per slot this chunk (0 = slot sits this step
    out entirely; the tail of its row is padding).  Returns
    ``(new_cache, metrics)`` with ``pos`` advanced by ``n_valid`` per slot.

    No logits: prefill chunks never sample — the next token after the
    prompt comes from feeding the FINAL prompt token through the regular
    decode step (bit-identical to token-by-token serving), so the unembed
    matmul over all S positions is skipped entirely.

    Uniform (dense-attention) family with a dense KV cache only: SSM and
    hybrid recurrences, and sliding-window ring buffers, consume their
    prompts token-by-token (the server's scheduler falls back for them).
    The serve-mode FFN dispatch (and the tick-scope plan) runs on the
    B*S chunk rows under a TOKEN-level mask — per-slot activity AND the
    per-token n_valid bound — so padded rows never touch the router,
    the capacity dispatch, or any invoke stat.
    """
    topo = topology(cfg)
    assert topo.kind == "uniform" and not cfg.sliding_window, \
        "decode_chunk needs the uniform family with a dense KV cache " \
        f"(got family={cfg.family!r}, sliding_window={cfg.sliding_window})"
    b, s = tokens.shape[0], tokens.shape[1]
    x = L.embed_fwd(cfg, params["embed"], tokens)
    pos = cache["pos"]                                   # (B,) per-slot
    positions = pos[:, None] + jnp.arange(s)[None, :]    # (B, S)
    tok_mask = jnp.arange(s)[None, :] < n_valid[:, None]
    if row_mask is not None:
        tok_mask = tok_mask & row_mask.astype(bool)[:, None]
    plan = None
    if serve and cfg.approx.enable:
        if cfg.approx.route_scope == "tick" and not cfg.moe.n_experts:
            from repro.models.approx_ffn import make_tick_plan
            plan = make_tick_plan(cfg, params, x, tok_mask, tier=tier,
                                  tier_margins=tier_margins,
                                  residency=residency)
            tier = tier_margins = None   # the plan embeds the tiers

    bt = cache.get("block_table")         # paged: ONE table for all layers

    def body(carry, blk_i):
        x, ck, cv = carry
        blk, i = blk_i
        with jax.named_scope(scopes.KV_GATHER):
            lc = {"k": ck[i], "v": cv[i], "pos": pos, "n_valid": n_valid}
        if bt is not None:
            lc["block_table"] = bt
        x, nc, _, m = _dense_block(cfg, blk, x, positions, lc, serve=serve,
                                   row_mask=tok_mask, dispatch_plan=plan,
                                   tier=tier, tier_margins=tier_margins,
                                   residency=residency)
        m.pop("_label_votes", None)   # train-only co-training signal
        with jax.named_scope(scopes.KV_WRITE):
            ck = jax.lax.dynamic_update_index_in_dim(ck, nc["k"], i, 0)
            cv = jax.lax.dynamic_update_index_in_dim(cv, nc["v"], i, 0)
        return (x, ck, cv), (m if collect_metrics else None)
    (_, ks, vs), ms = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.n_layers)))
    new_cache = {"k": ks, "v": vs, "pos": pos + n_valid.astype(jnp.int32)}
    if bt is not None:
        new_cache["block_table"] = bt
    metrics: dict[str, jax.Array] = {}
    if collect_metrics and ms is not None:
        metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}
    return new_cache, metrics


# ---------------------------------------------------------------------------
# Losses / steps
# ---------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, params, inputs, labels):
    """Next-token cross-entropy (+ family aux losses).  labels: (B, S).

    CE is computed as a one-hot contraction, not take_along_axis: a gather
    over a vocab-sharded logits tensor forces the SPMD partitioner into
    token replication ("involuntary full rematerialization"); the one-hot
    einsum shards cleanly (tokens over data, vocab over model).
    """
    from repro.sharding.activations import constrain_logits, constrain_tokens
    logits, _, aux, metrics = forward(cfg, params, inputs)
    logits = constrain_logits(logits).astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), -1))
    onehot = jax.nn.one_hot(labels, cfg.vocab, dtype=shifted.dtype)
    picked = jnp.einsum("bsv,bsv->bs", shifted, onehot)
    nll = constrain_tokens(lse - picked)
    loss = jnp.mean(nll)
    metrics = dict(metrics, lm_loss=loss, aux_loss=aux)
    return loss + aux, metrics
