"""Model assembly: every assigned architecture family as one functional LM.

Families and their block layouts:
  dense / audio / vlm : N x (attn + FFN)            [scan over layers]
  moe                 : N x (attn + MoE-FFN)        [scan over layers]
  ssm (xLSTM)         : G x ((k-1) mLSTM + 1 sLSTM) [scan over groups, inner
                        scan over the mLSTM run]    (k = ssm.slstm_every)
  hybrid (zamba2)     : G x (k mamba2 + SHARED attn/FFN block)  (k =
                        attn_every; the shared block's params are one set
                        applied at every group boundary — Zamba2's design)
  layered (granite-4.0-h): N x (mixer + FFN), each layer's mixer a Mamba2
                        or an attention as ``cfg.layer_types`` lists it,
                        each layer with its own FFN  [scan over periods of
                        the pattern, an inner scan over each run of one
                        mixer kind]

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
        x = _residual(cfg, x, h)
        f, aux, metrics = _ffn_part(cfg, p, L.norm_fwd(cfg, p["ln2"], x),
                                    serve, row_mask, dispatch_plan, tier,
                                    tier_margins, residency)
        x = _residual(cfg, x, f)
    return x, new_cache, aux, metrics


def _residual(cfg: ModelConfig, x, y):
    """x + a sublayer's output, scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


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


# ---- layered (granite-4.0-h) -----------------------------------------------

MAMBA, ATTENTION = "mamba", "attention"
MIXERS = (MAMBA, ATTENTION)


def _init_mixer(key, cfg, kind):
    core = (mamba2.init_mamba(key, cfg) if kind == MAMBA
            else L.init_attn(key, cfg))
    return {"ln": L.init_norm(cfg, cfg.d_model),
            ("core" if kind == MAMBA else "attn"): core}


def _init_layer_ffn(key, cfg):
    p = {"ln": L.init_norm(cfg, cfg.d_model)}
    if cfg.approx.enable:
        p["approx"] = init_approx_ffn(key, cfg)
    else:
        p["ffn"] = L.init_ffn(key, cfg)
    return p


@dataclasses.dataclass(frozen=True)
class Run:
    """Consecutive layers of one mixer kind inside a period of the
    layered pattern."""

    kind: str
    start: int           # first layer, within the period
    length: int
    first: int           # its index among the period's layers of its kind


def layer_runs(cfg: ModelConfig) -> tuple[int, tuple[Run, ...]]:
    """The shortest period that tiles ``cfg.layer_types``, and its runs.
    granite-4.0-h-micro's 40 layers are 4 periods of 10: five Mamba2
    layers, one attention layer, four Mamba2 layers."""
    types = tuple(cfg.layer_types)
    n = len(types)
    assert n == cfg.n_layers and set(types) <= set(MIXERS), (
        f"layer_types must give one of {MIXERS} per layer "
        f"(n_layers={cfg.n_layers}, got {types})")
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and types == types[:p] * (n // p))
    runs, seen = [], {k: 0 for k in MIXERS}
    for j, kind in enumerate(types[:period]):
        if runs and runs[-1].kind == kind:
            runs[-1] = dataclasses.replace(runs[-1],
                                           length=runs[-1].length + 1)
        else:
            runs.append(Run(kind, j, 1, seen[kind]))
        seen[kind] += 1
    return period, tuple(runs)


def _run_layered(cfg: ModelConfig, params, x, carry, layer, *,
                 remat: bool = False):
    """Every layer of the layered stack in order, as one scan over the
    periods of the pattern with an inner scan over each run of one mixer
    kind: one traced body per run of a period, whatever the depth.

    ``layer(kind, mix, ffn, i, x, carry) -> (x, carry, out)`` applies one
    layer: ``mix`` and ``ffn`` are its parameters, ``i`` (traced) its
    index among the layers of its kind (the row of that kind's stack in
    the parameters and the cache).  Returns ``(x, carry, outs)`` with
    each leaf of ``out`` stacked over the layers.  ``remat`` wraps each
    layer in jax.checkpoint (the training forward)."""
    period, runs = layer_runs(cfg)
    per = {k: cfg.layer_types[:period].count(k) for k in MIXERS}
    fn = jax.checkpoint if remat else (lambda f: f)

    def group(xc, g):
        outs = []
        for r in runs:
            def one(xc, j, r=r):
                x, carry = xc
                i = g * per[r.kind] + r.first + j
                mix = jax.tree.map(lambda a: a[i], params[r.kind])
                ffn = jax.tree.map(lambda a: a[g * period + r.start + j],
                                   params["ffn"])
                x, carry, out = layer(r.kind, mix, ffn, i, x, carry)
                return (x, carry), out
            if r.length == 1:
                xc, out = fn(one)(xc, 0)
                out = jax.tree.map(lambda a: a[None], out)
            else:
                xc, out = jax.lax.scan(fn(one), xc, jnp.arange(r.length))
            outs.append(out)
        return xc, jax.tree.map(lambda *a: jnp.concatenate(a), *outs)
    (x, carry), outs = jax.lax.scan(group, (x, carry),
                                    jnp.arange(cfg.n_layers // period))
    return x, carry, jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), outs)


def _layered_ffn(cfg: ModelConfig, p, x, **kw):
    """The layer's FFN sublayer: (x, aux, metrics)."""
    f, aux, m = _ffn_part(cfg, p, L.norm_fwd(cfg, p["ln"], x), **kw)
    return _residual(cfg, x, f), aux, m


def _mamba_mixer(cfg: ModelConfig, mix, x, states, i, tok_mask):
    """The Mamba2 sublayer of layer ``i`` of the mixer stack against the
    cache's state stacks ``states`` ((n_mamba, B, ...) leaves)."""
    with jax.named_scope(scopes.SSM_MIXER), \
            jax.named_scope(scopes.SSM_STATE):
        st = jax.tree.map(lambda a: a[i], states)
    y, st = mamba2.mamba_fwd(cfg, mix["core"], L.norm_fwd(cfg, mix["ln"], x),
                             st, tok_mask=tok_mask)
    with jax.named_scope(scopes.SSM_MIXER), \
            jax.named_scope(scopes.SSM_STATE):
        states = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(
                a, n.astype(a.dtype), i, 0), states, st)
    return _residual(cfg, x, y), states


# ---------------------------------------------------------------------------
# Model topology descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """How layers group into scan stacks for a family."""

    kind: str            # "uniform" | "xlstm" | "hybrid" | "layered"
    n_groups: int = 0
    per_group: int = 0   # inner homogeneous run length (layered: the
                         # pattern's period)


def topology(cfg: ModelConfig) -> Topology:
    if cfg.family == "layered":
        assert not cfg.moe.n_experts and not cfg.sliding_window, \
            "the layered family has dense FFNs and full attention"
        period, _ = layer_runs(cfg)
        return Topology("layered", cfg.n_layers // period, period)
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
    elif topo.kind == "layered":
        # layer i draws its mixer and its FFN from the two halves of its
        # own key; each kind's mixers stack in layer order
        keys = jax.vmap(jax.random.split)(
            jax.random.split(kb, cfg.n_layers))
        for kind in MIXERS:
            idx = [i for i, t in enumerate(cfg.layer_types) if t == kind]
            if idx:
                params[kind] = jax.vmap(
                    lambda k, kind=kind: _init_mixer(k, cfg, kind))(
                        keys[jnp.asarray(idx), 0])
        params["ffn"] = jax.vmap(lambda k: _init_layer_ffn(k, cfg))(
            keys[:, 1])
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
            and topo.kind in ("uniform", "hybrid", "layered"):
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

    elif topo.kind == "hybrid":
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

    elif topo.kind == "layered":
        assert not collect_cache, \
            "the layered family prefills through decode_chunk"
        plan = None
        if serve and cfg.approx.enable and cfg.approx.route_scope == "tick":
            from repro.models.approx_ffn import make_tick_plan
            plan = make_tick_plan(cfg, params, x)

        def layer(kind, mix, ffn, i, x, votes):
            if kind == MAMBA:
                y, _ = mamba2.mamba_fwd(cfg, mix["core"],
                                        L.norm_fwd(cfg, mix["ln"], x))
            else:
                y, _ = L.attention_fwd(cfg, mix["attn"],
                                       L.norm_fwd(cfg, mix["ln"], x),
                                       positions)
            x, aux, m = _layered_ffn(cfg, ffn, _residual(cfg, x, y),
                                     serve=serve, dispatch_plan=plan)
            if "_label_votes" in m:
                votes = votes + m.pop("_label_votes")
            return constrain(x), votes, (aux, m)
        x, votes, (auxs, ms) = _run_layered(cfg, params, x, votes0, layer,
                                            remat=cfg.remat)
        aux_total = jnp.sum(auxs)
        metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}

    if train_tick and topo.kind in ("uniform", "hybrid", "layered"):
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

    ``page_size > 0`` builds the PAGED layout (uniform and layered
    families): k/v are per-layer pools ``(L, kv_pages, page_size,
    *layers.pool_rows(cfg))`` (Kh rows of hd; narrow heads packed) plus
    ONE ``block_table`` (batch, max_len // page_size) shared by every
    layer — all layers write the same positions, so one table
    serves the stack.  ``page_size=0`` keeps the dense ``(L, batch,
    max_len, Kh, hd)`` layout, the bit-exact oracle.

    The layered family's k/v stacks hold its attention layers alone, and
    beside them ``mamba`` holds each Mamba2 layer's recurrent and conv
    state, ``(n_mamba, batch, ...)`` leaves: one slot's state is a
    fixed size, so it is not paged."""
    topo = topology(cfg)
    if topo.kind == "layered":
        n = {k: cfg.layer_types.count(k) for k in MIXERS}
        stack = lambda a, k: jnp.broadcast_to(a, (n[k], *a.shape))
        c = {"mamba": jax.tree.map(lambda a: stack(a, MAMBA),
                                   mamba2.init_mamba_state(cfg, batch))}
        ac = L.init_attn_cache(cfg, batch, max_len, page_size=page_size,
                               n_pages=kv_pages)
        c["k"], c["v"] = stack(ac["k"], ATTENTION), stack(ac["v"], ATTENTION)
        if page_size:
            c["block_table"] = ac["block_table"]
        c["pos"] = jnp.zeros((batch,), jnp.int32)
        return c
    if page_size:
        assert topo.kind == "uniform" and not cfg.sliding_window, (
            "paged KV caches need the uniform or layered family with "
            f"full attention (got family={cfg.family!r}, "
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


def reset_slot(cfg: ModelConfig, cache, slot):
    """Reset one batch slot of a decode cache to what ``init_cache``
    makes: continuous batching admits a new request into a freed slot.
    ``slot`` may be traced, so one compiled reset serves every slot.
    Batch-dim position per leaf: k/v (L, B, ...) -> 1; hybrid mamba and
    mlstm states (G, P, B, ...) -> 2; layered mamba states (n_mamba, B,
    ...) and slstm states (G, B, ...) -> 1; pos, block_table -> 0.  The
    slot's values come from a one-slot ``init_cache``, constants under
    jit, so the init functions stay the one source of initial values.

    Paged caches (``block_table`` present): the k/v pools are SHARED by
    every slot — freeing pages is the server allocator's job, so the
    pools pass through untouched and only the slot's block-table row
    (back to -1), its ``pos`` (back to 0) and any recurrent state reset."""
    paged = "block_table" in cache
    layered = topology(cfg).kind == "layered"
    page = cache["k"].shape[2] if paged else 0
    if paged:
        max_len = cache["block_table"].shape[1] * page
    else:
        max_len = cache["k"].shape[2] if "k" in cache else 1
    fresh = init_cache(cfg, 1, max_len, page_size=page,
                       kv_pages=1 if paged else 0)

    def bdim(path):
        head = path[0]
        if head in ("k", "v"):
            return None if paged else 1
        if head in ("mlstm", "mamba"):
            return 1 if layered else 2
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
        return c.at[idx].set(jnp.take(f, 0, axis=d))
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


def _decode_kv_plan(cfg: ModelConfig, cache, row_mask):
    """(block table or None, whether the step reads its pages in place,
    tokens each slot attends there — a masked slot fetches nothing)."""
    bt = cache.get("block_table")         # paged: ONE table for all layers
    in_place = bt is not None and _pages_read_in_place(cfg)
    lengths = None
    if in_place:
        lengths = held_lengths(cache["pos"], bt, cache["k"].shape[2])
        if row_mask is not None:
            lengths = jnp.where(row_mask, lengths, 0)
    return bt, in_place, lengths


def _layer_kv(ck, cv, i, pos, bt, in_place, lengths):
    """The attention cache of layer ``i`` of the k/v stacks for a decode
    step: the stacks themselves when the paged kernel reads them in
    place, else the layer's pool (or dense window) sliced out."""
    if in_place:
        return {"k": ck, "v": cv, "pos": pos, "block_table": bt,
                "layer": i, "lengths": lengths}
    with jax.named_scope(scopes.KV_GATHER):
        lc = {"k": ck[i], "v": cv[i], "pos": pos}
    if bt is not None:
        lc["block_table"] = bt
    return lc


def _store_kv(ck, cv, nc, i, in_place):
    """The k/v stacks after layer ``i`` wrote its cache ``nc``."""
    if in_place:
        return nc["k"], nc["v"]
    with jax.named_scope(scopes.KV_WRITE):
        ck = jax.lax.dynamic_update_index_in_dim(ck, nc["k"], i, 0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, nc["v"], i, 0)
    return ck, cv


def decode(cfg: ModelConfig, params, cache, inputs: jax.Array, *,
           serve: bool = True, collect_metrics: bool = False,
           row_mask: jax.Array | None = None,
           tier: jax.Array | None = None,
           tier_margins: jax.Array | None = None,
           residency: jax.Array | None = None):
    """One decode step.  inputs: tokens (B, 1) or embeds (B, 1, d).
    Returns (logits (B, V), new_cache), or (logits, new_cache, metrics)
    when ``collect_metrics`` — layer-meaned per-step block metrics (e.g.
    the ApproxFFN dispatch invocation rate).

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
                and topo.kind in ("uniform", "hybrid", "layered")):
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
        bt, in_place, lengths = _decode_kv_plan(cfg, cache, row_mask)

        def body(carry, blk_i):
            x, ck, cv = carry
            blk, i = blk_i
            lc = _layer_kv(ck, cv, i, pos, bt, in_place, lengths)
            x, nc, _, m = _dense_block(cfg, blk, x, positions, lc, serve=serve,
                                       row_mask=row_mask, dispatch_plan=plan,
                                       tier=tier, tier_margins=tier_margins,
                                       residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            ck, cv = _store_kv(ck, cv, nc, i, in_place)
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

    elif topo.kind == "layered":
        # the step's masked rows keep their recurrent state, as their pos
        bt, in_place, lengths = _decode_kv_plan(cfg, cache, row_mask)
        tok_mask = None if row_mask is None \
            else row_mask.astype(bool)[:, None]

        def layer(kind, mix, ffn, i, x, carry):
            states, ck, cv = carry
            if kind == MAMBA:
                x, states = _mamba_mixer(cfg, mix, x, states, i, tok_mask)
            else:
                lc = _layer_kv(ck, cv, i, pos, bt, in_place, lengths)
                y, nc = L.attention_fwd(cfg, mix["attn"],
                                        L.norm_fwd(cfg, mix["ln"], x),
                                        positions, lc)
                ck, cv = _store_kv(ck, cv, nc, i, in_place)
                x = _residual(cfg, x, y)
            x, _, m = _layered_ffn(cfg, ffn, x, serve=serve,
                                   row_mask=row_mask, dispatch_plan=plan,
                                   tier=tier, tier_margins=tier_margins,
                                   residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            return x, (states, ck, cv), (m if collect_metrics else None)
        x, (states, ks, vs), ms = _run_layered(
            cfg, params, x, (cache["mamba"], cache["k"], cache["v"]), layer)
        adv = 1 if row_mask is None else row_mask.astype(jnp.int32)
        new_cache = {"mamba": states, "k": ks, "v": vs, "pos": pos + adv}
        if bt is not None:
            new_cache["block_table"] = bt
        if collect_metrics and ms is not None:
            step_metrics = {k: jnp.mean(v, axis=0) for k, v in ms.items()}

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

    Uniform and layered families with full attention: the xLSTM and
    zamba2 recurrences, and sliding-window ring buffers, consume their
    prompts token-by-token (the server's scheduler falls back for them).
    A layered model's Mamba2 layers run the chunked SSD from each slot's
    cached state, over the slot's valid tokens only.
    The serve-mode FFN dispatch (and the tick-scope plan) runs on the
    B*S chunk rows under a TOKEN-level mask — per-slot activity AND the
    per-token n_valid bound — so padded rows never touch the router,
    the capacity dispatch, or any invoke stat.
    """
    topo = topology(cfg)
    assert topo.kind in ("uniform", "layered") and not cfg.sliding_window, \
        "decode_chunk needs the uniform or layered family with full " \
        f"attention (got family={cfg.family!r}, " \
        f"sliding_window={cfg.sliding_window})"
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

    def chunk_kv(ck, cv, i):
        with jax.named_scope(scopes.KV_GATHER):
            lc = {"k": ck[i], "v": cv[i], "pos": pos, "n_valid": n_valid}
        if bt is not None:
            lc["block_table"] = bt
        return lc

    if topo.kind == "layered":
        # each Mamba2 layer starts from its slot's state, and the tokens
        # past a slot's n_valid (or of a masked slot) leave it as it was
        def layer(kind, mix, ffn, i, x, carry):
            states, ck, cv = carry
            if kind == MAMBA:
                x, states = _mamba_mixer(cfg, mix, x, states, i, tok_mask)
            else:
                y, nc = L.attention_fwd(cfg, mix["attn"],
                                        L.norm_fwd(cfg, mix["ln"], x),
                                        positions, chunk_kv(ck, cv, i))
                ck, cv = _store_kv(ck, cv, nc, i, False)
                x = _residual(cfg, x, y)
            x, _, m = _layered_ffn(cfg, ffn, x, serve=serve,
                                   row_mask=tok_mask, dispatch_plan=plan,
                                   tier=tier, tier_margins=tier_margins,
                                   residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            return x, (states, ck, cv), (m if collect_metrics else None)
        _, (states, ks, vs), ms = _run_layered(
            cfg, params, x, (cache["mamba"], cache["k"], cache["v"]), layer)
        new_cache = {"mamba": states}
    else:
        def body(carry, blk_i):
            x, ck, cv = carry
            blk, i = blk_i
            lc = chunk_kv(ck, cv, i)
            x, nc, _, m = _dense_block(cfg, blk, x, positions, lc,
                                       serve=serve, row_mask=tok_mask,
                                       dispatch_plan=plan, tier=tier,
                                       tier_margins=tier_margins,
                                       residency=residency)
            m.pop("_label_votes", None)   # train-only co-training signal
            ck, cv = _store_kv(ck, cv, nc, i, False)
            return (x, ck, cv), (m if collect_metrics else None)
        (_, ks, vs), ms = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        new_cache = {}
    new_cache.update(k=ks, v=vs, pos=pos + n_valid.astype(jnp.int32))
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
