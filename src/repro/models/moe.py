"""Mixture-of-Experts FFN with top-k routing, capacity, and SORT-BASED
dispatch (MegaBlocks-style on XLA).

The classic GShard one-hot dispatch materializes a (T, E, cap) tensor —
at LM scale (1M tokens x 64 experts) that is petabytes.  Instead we:

  1. argsort the (token, choice) pairs by expert id;
  2. compute each pair's rank within its expert (static-shape cumsum math);
  3. scatter tokens into a (E*cap, d) buffer (over-capacity pairs drop to a
     trash slot — the GShard convention, residual carries dropped tokens);
  4. run the experts as one batched (E, cap, d) x (E, d, f) matmul — this
     shards as EP (experts over "model") or TP-in-expert (sharding/rules);
  5. gather back and combine with the (renormalized) gate weights.

Memory is O(E*cap*d + T*d); FLOPs scale with top_k * capacity_factor.
Aux load-balancing loss follows Switch/GShard: E * sum_e(f_e * p_e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {"router": jax.random.normal(ks[0], (d, e), cfg.pdtype) * s_in,
         "w_in": jax.random.normal(ks[1], (e, d, f), cfg.pdtype) * s_in,
         "w_out": jax.random.normal(ks[2], (e, f, d), cfg.pdtype) * s_out}
    if cfg.gated_ffn:
        p["w_gate"] = jax.random.normal(ks[3], (e, d, f), cfg.pdtype) * s_in
    return p


def moe_fwd(cfg: ModelConfig, p, x: jax.Array):
    """x: (B, S, d) -> (out, aux_loss).

    Distributed path (under a mesh + activation-sharding context): the
    dispatch is shard_mapped over the DATA axes — each data shard sorts and
    routes only its LOCAL tokens (GShard groups == data shards), while the
    expert matmuls stay in GSPMD auto mode over the model axis (EP or
    TP-in-expert per sharding/rules).  Without this, the global argsort
    forces the partitioner to replicate the (T*k, d) gather/scatter and
    all-reduce it — measured at ~250 s/step of wire time on the 64-expert
    train cell (§Perf B.1).  Expert weights are FSDP-stored over data and
    all-gathered here (classic FSDP unshard-on-use).

    Local path (tests, single host): GShard-style token groups of
    ``moe.scan_chunk`` via lax.scan, checkpointed per group.
    """
    from repro.sharding.activations import manual_dp_context
    mesh, dp = manual_dp_context()
    if mesh is not None and "model" in mesh.axis_names:
        md = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        if cfg.moe.n_experts % md == 0:
            return _moe_fwd_manual(cfg, p, x, mesh, dp, md)
        # TP-in-expert archs (E < |model|) stay on the auto path
    return _moe_chunked(cfg, p, x)


def _moe_fwd_manual(cfg: ModelConfig, p, x, mesh, dp, md):
    """Fully-manual expert parallelism (shard_map over ALL mesh axes).

    Each model shard owns E/|model| experts; x is replicated across the
    model axis (standard for the residual stream), so dispatch needs NO
    token movement: every model shard routes its data-shard's tokens to its
    own experts, computes them, and the partial outputs are psum'd over
    "model" — exactly one all-reduce of the token block per layer, the same
    as a dense TP FFN.  FSDP weight storage is unsharded on use with one
    all_gather over the data axes.  Capacity is per (data shard, expert):
    GShard groups == data shards.
    """
    from repro.sharding.rules import moe_manual_specs
    e = cfg.moe.n_experts
    specs = moe_manual_specs(mesh, gated="w_gate" in p)
    axes = tuple(dp) + ("model",)

    def local(p_loc, x_loc):
        # unshard: router fully, expert weights over the FSDP (data) dim
        router = jax.lax.all_gather(
            jax.lax.all_gather(p_loc["router"], "model", axis=1, tiled=True),
            dp, axis=0, tiled=True)
        w = {k: jax.lax.all_gather(p_loc[k], dp, axis=1, tiled=True)
             for k in ("w_in", "w_out", "w_gate") if k in p_loc}
        e_loc = e // md
        e_off = jax.lax.axis_index("model") * e_loc
        y_part, aux = _moe_local_experts(cfg, router, w, x_loc, e_loc, e_off)
        y = jax.lax.psum(y_part, "model")
        return y, jax.lax.pmean(aux, axes)

    fn = jax.shard_map(local, mesh=mesh, in_specs=specs["in"],
                       out_specs=specs["out"],
                       axis_names=frozenset(axes), check_vma=False)
    return fn(p, x)


def _moe_local_experts(cfg: ModelConfig, router, w, x, e_loc: int, e_off):
    """Route local tokens to THIS shard's experts (global top-k routing,
    local compute).  x: (B, S, d) local tokens; returns the partial output
    (zeros for tokens whose experts live elsewhere) and the aux loss.

    Dispatch plumbing (sort-based ranks, capacity slots, trash-slot
    scatter/gather) is the shared machinery of the serving dispatch engine
    (runtime/dispatch.py) — one implementation for MoE and MCMA."""
    from repro.runtime import dispatch as D
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    t = b * s
    cap = min(int(cfg.moe.capacity_factor * t * k / e) + 1, t)
    xt = x.reshape(t, d)

    logits = jnp.dot(xt, router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # global-expert rank math (capacity consistent across model shards);
    # only classes [e_off, e_off + e_loc) land in this shard's buffer
    e_flat = gate_idx.reshape(t * k)
    tok_flat = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(t * k)
    order, e_sorted, rank, _ = D.class_sort_ranks(e_flat, e)
    keep, slot = D.capacity_slots(e_sorted, rank, cap, n_local=e_loc,
                                  offset=e_off)

    xe = D.scatter_rows(xt[tok_flat[order]], slot, keep,
                        e_loc * cap).reshape(e_loc, cap, d)

    h = jnp.einsum("ecd,edf->ecf", xe, w["w_in"].astype(x.dtype))
    if cfg.gated_ffn:
        g = jnp.einsum("ecd,edf->ecf", xe, w["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.silu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, w["w_out"].astype(x.dtype))

    contrib = D.gather_rows(ye.reshape(e_loc * cap, d), slot, keep) \
        * gate_vals.reshape(t * k)[order][:, None].astype(ye.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_flat[order]].add(contrib)

    frac_tokens = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e,
                                          dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs) * cfg.moe.aux_weight
    return out.reshape(b, s, d), aux


def _moe_chunked(cfg: ModelConfig, p, x: jax.Array):
    """Token groups of ``moe.scan_chunk`` via lax.scan (bounds buffers)."""
    b, s, d = x.shape
    t = b * s
    ck = cfg.moe.scan_chunk
    if ck and t > ck and t % ck == 0:
        xg = x.reshape(t // ck, 1, ck, d)

        def one(_, xc):
            y, aux = _moe_group(cfg, p, xc)
            return None, (y, aux)
        # checkpoint per group: backward re-dispatches a group instead of
        # saving every group's (E, cap, d/f) buffers
        _, (yg, auxg) = jax.lax.scan(jax.checkpoint(one), None, xg)
        return yg.reshape(b, s, d), jnp.mean(auxg)
    return _moe_group(cfg, p, x)


def _moe_group(cfg: ModelConfig, p, x: jax.Array):
    """One token group.  x: (B, S, d) -> (out, aux_loss)."""
    from repro.runtime import dispatch as D
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    t = b * s
    cap = min(int(cfg.moe.capacity_factor * t * k / e) + 1, t)
    xt = x.reshape(t, d)

    logits = jnp.dot(xt, p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                          # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch (shared engine plumbing) -----------------------
    e_flat = gate_idx.reshape(t * k)                                       # (T*k,)
    tok_flat = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(t * k)
    order, e_sorted, rank, _ = D.class_sort_ranks(e_flat, e)
    keep, slot = D.capacity_slots(e_sorted, rank, cap, n_local=e)

    # scatter tokens into the expert buffer
    xe = D.scatter_rows(xt[tok_flat[order]], slot, keep,
                        e * cap).reshape(e, cap, d)

    h = jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(x.dtype))
    if cfg.gated_ffn:
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.silu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(x.dtype))

    # gather back + weighted combine (scatter-add over the k choices)
    contrib = D.gather_rows(ye.reshape(e * cap, d), slot, keep) \
        * gate_vals.reshape(t * k)[order][:, None].astype(ye.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_flat[order]].add(contrib)

    # Switch-style load-balancing aux loss
    frac_tokens = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                           axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs) * cfg.moe.aux_weight
    return out.reshape(b, s, d), aux
