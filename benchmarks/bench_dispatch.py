"""Dispatch-engine microbench: XLA capacity dispatch vs the Pallas
weight-switch engine (runtime/dispatch.py) across batch sizes and
approximator counts.

On CPU the Pallas backend runs in interpreter mode, so its wall-time
column measures dispatch/plumbing overhead, not kernel speed (the kernel
target is TPU v5e — rerun there with interpret off for real numbers).
The XLA column IS a meaningful portable baseline, and both rows carry the
invoke_stats the engine reports (invocation rate, dropped rows, executed
vs useful rows) so the capacity/padding economics are visible per shape.

    PYTHONPATH=src python -m benchmarks.bench_dispatch [--quick]
    PYTHONPATH=src python -m benchmarks.bench_dispatch --quick --devices 8

``--devices N`` adds a sharded mode: the same shapes run through
``mcma_dispatch_sharded`` over an N-way data mesh (forcing N virtual CPU
devices when needed), recording the global sharded wall time next to a
shard-local single-device baseline (one shard's rows on one device) so
the scaling overhead of the shard_map path is visible per shape.  Every
mode asserts the Pallas backend against the XLA oracle.

``--autotune`` adds the capacity-autotuning microbench: a skewed,
phase-shifting synthetic request mix is served tick by tick while the
``runtime/autotune.CapacityController`` walks its operating-point ladder
(with ``--devices N`` also through the sharded engine on an N-way mesh).
Each tick appends a trajectory row (operating point, dropped rows,
routed vs served invocation); at every VISITED operating point the
Pallas backend is asserted against the XLA oracle — the divergence gate
under switched capacities.  The leg itself asserts the controller ends
under the drop budget with strictly more served invocation than the
static starting point.

``--decode-tick`` adds the tick-level dispatch-planning microbench: a
full L-layer decode tick through the REAL model decode step in
``route_scope="layer"`` (route -> class-sort -> dispatch inside every
layer of the scan) vs ``route_scope="tick"`` (ONE DispatchPlan above the
scan, every layer a weight-switch launch on already-sorted rows).  Each
row records the per-tick wall time plus the DYNAMIC sort/scatter op
counts per tick (jaxpr walk, scan-length aware) — the leg asserts tick
scope runs strictly fewer sorts (1 plan vs L) and gates the Pallas
backend against the XLA oracle at both scopes.

``--backend-sweep`` adds the fused-kernel comparison: every backend
(``xla`` | ``pallas`` | ``pallas_fused``) serves an L-layer tick from
one DispatchPlan (``lax.scan`` of ``execute_dispatch`` over per-layer
weight banks), recording ms_per_tick plus the STANDALONE activation
gather/scatter counts per layer from a jaxpr audit
(``repro.analysis.opcount.activation_moves`` — rank >= 2 operands,
pallas kernel bodies excluded).  Gates: the fused backend runs <= 1
standalone gather and <= 1 scatter per layer (strictly fewer than
unfused pallas, whose class-sort legs it folds into the kernel), is
bitwise equal to unfused pallas and < 1e-4 vs the XLA oracle at every
visited operating point (uniform + asymmetric caps, masked rows, tiered
margins), retraces nothing across plan changes, and in interpret mode
is no slower than unfused.  ``--devices N`` adds a sharded pass.

``--qos`` adds the per-request QoS tier-mix sweep: batches mixing
error-bound tiers (tight/base/loose exact-logit margins, a traced
vector — one compiled program per operating point serves every mix) run
at several capacity rungs including an asymmetric per-class one; each
mix is pallas-vs-xla gated and the leg asserts loose-bound rows serve
strictly more invocation than tight-bound rows in the same batch at
every visited operating point, with per-tier margin/rows/served-
invocation columns in the CSV.

Writes benchmarks/out/dispatch.csv (modes: single | sharded |
shard-local | autotune | decode-tick | qos | backend-sweep |
backend-sweep-sharded).
"""
from __future__ import annotations

import argparse
import csv
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jit_cache import assert_zero_retrace
from repro.analysis.opcount import activation_moves, count_dynamic_ops
from repro.launch.mesh import make_mesh
from repro.runtime import dispatch as D

OUT = os.path.join(os.path.dirname(__file__), "out")


def _make_case(key, t, n, d, d_h, d_ff):
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (d, n + 1)) * 0.5
    w1 = jax.random.normal(ks[2], (n, d, d_h)) * 0.2
    b1 = jnp.zeros((n, d_h))
    w2 = jax.random.normal(ks[3], (n, d_h, d)) * 0.2
    b2 = jnp.zeros((n, d))
    wi = jax.random.normal(ks[4], (d, d_ff)) * 0.1
    wo = jax.random.normal(ks[5], (d_ff, d)) * 0.1
    return x, x @ router, (w1, b1, w2, b2), (wi, wo)


def _time(fn, *args, iters):
    y, _ = fn(*args)
    jax.block_until_ready(y)                     # compile outside the clock
    t0 = time.perf_counter()
    for _ in range(iters):
        y, stats = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters * 1e3, stats


def _record(rows, *, t, n, d, backend, block_t, interpret, ms, stats,
            devices, mode):
    row = {
        "T": t, "n_approx": n, "d_model": d, "backend": backend,
        "block_t": block_t, "interpret": interpret,
        "devices": devices, "mode": mode,
        "ms_per_call": round(ms, 3),
        "invocation": round(float(stats["invocation"]), 4),
        "exact_frac": round(float(stats["exact_frac"]), 4),
        "dropped": int(stats["dropped"]),
        "executed_rows": int(stats["executed_rows"]),
        "padding_rows": int(stats["padding_rows"]),
    }
    rows.append(row)
    print(f"T={t:6d} n={n} {mode:11s} x{devices} {backend:6s} "
          f"{ms:9.2f} ms/call inv={row['invocation']:.3f} "
          f"pad_rows={row['padding_rows']}", flush=True)
    return row


def _check_oracle(rows, outs, t, n):
    """Gate: the Pallas backend must match the XLA oracle on every row."""
    err = float(np.abs(outs["pallas"] - outs["xla"]).max())
    for row in rows[-2:]:
        row["max_abs_err_vs_xla"] = round(err, 7) \
            if row["backend"] == "pallas" else 0.0
    assert err < 1e-4, f"backend divergence at T={t} n={n}: {err}"


def _skewed_logits(key, t, n, hot, hot_frac):
    """Router logits sending ~hot_frac of rows to class ``hot`` and the
    rest roughly uniform over the other classes (incl. exact)."""
    ks = jax.random.split(key, 2)
    cls = jnp.where(jax.random.uniform(ks[0], (t,)) < hot_frac, hot,
                    jax.random.randint(ks[1], (t,), 0, n + 1))
    return jax.nn.one_hot(cls, n + 1) * 10.0


def _autotune_leg(rows, *, quick, devices, drop_budget=0.05):
    """Serve a phase-shifting skewed mix through the controller's ladder;
    gate pallas-vs-xla at every visited operating point."""
    from repro.runtime.autotune import (CapacityController, OperatingPoint,
                                        point_caps)
    from repro.sharding.rules import shard_capacity
    t, n = (256, 3) if quick else (1024, 4)
    d, d_h, d_ff, block_t = (128, 32, 256, 64) if quick \
        else (256, 64, 1024, 128)
    on_cpu = jax.default_backend() != "tpu"
    key = jax.random.PRNGKey(17)
    x, _, (w1, b1, w2, b2), (wi, wo) = _make_case(key, t, n, d, d_h, d_ff)
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])

    ladder = (OperatingPoint(0.5, 0.15), OperatingPoint(0.5, 0.3),
              OperatingPoint(0.6, 0.5), OperatingPoint(1.0, 1.0))
    mesh = make_mesh((devices,), ("data",)) if devices > 1 else None
    tl = t // devices
    ctrl = CapacityController(
        ladder, lambda pt: point_caps(pt, tl, n, n_shards=devices),
        drop_budget=drop_budget, cooldown=1, down_patience=4)

    fns = {}                                  # (rung, backend) -> jitted fn

    def run_point(idx, xx, lg, backend):
        pt = ladder[idx]
        ec = shard_capacity(tl, pt.exact_frac, slack=pt.shard_slack)
        ic = shard_capacity(tl, pt.invoke_frac, slack=pt.shard_slack)
        interp = on_cpu and backend == "pallas"
        if (idx, backend) not in fns:
            if mesh is None:
                fns[(idx, backend)] = jax.jit(
                    lambda a, b, be=backend, ip=interp, e=ec, i=ic:
                    D.mcma_dispatch(a, b, exact_fn, w1, b1, w2, b2,
                                    exact_cap=e, invoke_cap=i, backend=be,
                                    block_t=block_t, interpret=ip))
            else:
                fns[(idx, backend)] = jax.jit(
                    lambda a, b, be=backend, ip=interp, e=ec, i=ic:
                    D.mcma_dispatch_sharded(
                        mesh, a, b, exact_fn_p, (wi, wo), w1, b1, w2, b2,
                        exact_cap=e, invoke_cap=i, backend=be,
                        block_t=block_t, interpret=ip))
        return fns[(idx, backend)](xx, lg)

    # two-phase mix: light/balanced, then one class runs hot — the static
    # starting rung drops a large share of approximable rows
    phases = [(0.25, 8), (0.85, 24)] if quick else [(0.25, 10), (0.85, 40)]
    tick = 0
    static_idx = ctrl.index
    static_acc = np.zeros(2)                  # dropped, served approx rows
    tuned_acc = np.zeros(2)
    total_rows = 0
    hot = n                                   # hottest approximator class
    for hot_frac, ticks in phases:
        for _ in range(ticks):
            lg = _skewed_logits(jax.random.fold_in(key, tick), t, n, hot,
                                hot_frac)
            yx, sx = run_point(ctrl.index, x, lg, "xla")
            yp, sp = run_point(ctrl.index, x, lg, "pallas")
            err = float(np.abs(np.asarray(yp) - np.asarray(yx)).max())
            assert err < 1e-4, \
                f"pallas-vs-xla divergence at operating point " \
                f"{ladder[ctrl.index]}: {err}"
            # static baseline: the same mix pinned at the starting rung
            # (free while the controller still sits on it)
            ss = sx if ctrl.index == static_idx \
                else run_point(static_idx, x, lg, "xla")[1]
            static_acc += (float(ss["dropped"]),
                           float(np.asarray(ss["dispatched"])[1:].sum()))
            tuned_acc += (float(sx["dropped"]),
                          float(np.asarray(sx["dispatched"])[1:].sum()))
            total_rows += t
            pt = ladder[ctrl.index]
            rows.append({
                "T": t, "n_approx": n, "d_model": d, "backend": "both",
                "block_t": block_t, "interpret": on_cpu,
                "devices": devices, "mode": "autotune",
                "tick": tick, "op_index": ctrl.index,
                "op_exact_frac": pt.exact_frac,
                "op_invoke_frac": pt.invoke_frac,
                "invocation": round(float(sx["invocation"]), 4),
                "exact_frac": round(float(sx["exact_frac"]), 4),
                "dropped": int(sx["dropped"]),
                "served_invocation": round(
                    float(np.asarray(sx["dispatched"])[1:].sum())
                    / max(float(np.asarray(sx["class_counts"]).sum()), 1),
                    4),
                "executed_rows": int(sx["executed_rows"]),
                "padding_rows": int(sx["padding_rows"]),
                "max_abs_err_vs_xla": round(err, 7),
            })
            ctrl.observe(jax.tree.map(np.asarray, sx))
            tick += 1
    final = ctrl.index
    print(f"autotune x{devices}: {len(ctrl.history)} switches, final point "
          f"{ladder[final]}; dropped {tuned_acc[0]:.0f} vs static "
          f"{static_acc[0]:.0f} rows; served approx rows "
          f"{tuned_acc[1]:.0f} vs static {static_acc[1]:.0f}", flush=True)
    # the leg's own acceptance gate: under budget, strictly above static
    assert static_acc[0] / total_rows > 0.10, \
        "mix not skewed enough to stress the static config"
    last = [r for r in rows if r["mode"] == "autotune"][-max(
        1, phases[-1][1] // 2):]
    tail_drop = sum(r["dropped"] for r in last) / (len(last) * t)
    assert tail_drop <= drop_budget, (tail_drop, drop_budget)
    assert tuned_acc[1] > static_acc[1], \
        "autotune must serve strictly more approximator rows than static"


def _qos_leg(rows, *, quick, devices=1):
    """Per-request QoS tier-mix sweep: one batch mixing error-bound tiers
    through the tiered engine (exact-logit margins, a traced vector — the
    jitted program is shared by every mix) at several operating points,
    including an asymmetric per-class-capacity rung.  Gates pallas vs the
    XLA oracle per mix, and asserts the QoS contract: loose-bound rows
    serve STRICTLY more invocation than tight-bound rows in the same
    batch at every visited operating point."""
    from repro.runtime.autotune import (OperatingPoint, default_tier_bounds,
                                        margins_from_bounds)
    from repro.sharding.rules import shard_capacity
    t, n = (256, 3) if quick else (1024, 4)
    d, d_h, d_ff, block_t = (128, 32, 256, 64) if quick \
        else (256, 64, 1024, 128)
    on_cpu = jax.default_backend() != "tpu"
    key = jax.random.PRNGKey(23)
    x, lg, (w1, b1, w2, b2), (wi, wo) = _make_case(key, t, n, d, d_h, d_ff)
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])

    base_bound = 0.10
    bounds = default_tier_bounds(base_bound)      # tight / base / loose
    nt = len(bounds)
    # calibrate the margin scale to THIS router's logit spread so the
    # tier bias actually moves borderline rows (margins are traced — a
    # production server recalibrates without recompiling)
    scale = 1.5 * float(jnp.std(lg)) / float(np.log(2.0))
    margins = jnp.asarray(margins_from_bounds(bounds, base_bound,
                                              scale=scale), jnp.float32)
    # visited operating points: a tight rung where capacity BINDS (drops
    # visible in the per-tier columns), an ASYMMETRIC per-class rung (hot
    # class 1 gets the capacity the cold tail gives up), and the
    # full-capacity escape rung
    asym = tuple(round(f, 3) for f in np.linspace(0.5, 0.15, n))
    points = [OperatingPoint(0.5, 0.15),
              OperatingPoint(0.5, max(asym), invoke_fracs=asym),
              OperatingPoint(1.0, 1.0)]
    mixes = [(1, 1, 1), (3, 1, 1), (1, 1, 3)]    # balanced / tight- / loose-heavy

    mesh = make_mesh((devices,), ("data",)) if devices > 1 else None
    tl = t // devices
    fns = {}                                  # (rung, backend) -> jitted fn

    def run_point(idx, tier, backend):
        pt = points[idx]
        ec = shard_capacity(tl, pt.exact_frac, slack=pt.shard_slack)
        ic = tuple(shard_capacity(tl, f, slack=pt.shard_slack)
                   for f in pt.class_fracs(n))
        interp = on_cpu and backend == "pallas"
        if (idx, backend) not in fns:
            if mesh is None:
                fns[(idx, backend)] = jax.jit(
                    lambda a, b, tr, tm, be=backend, ip=interp, e=ec, i=ic:
                    D.mcma_dispatch(a, b, exact_fn, w1, b1, w2, b2,
                                    exact_cap=e, invoke_cap=i, backend=be,
                                    block_t=block_t, interpret=ip,
                                    tier=tr, tier_margins=tm))
            else:
                fns[(idx, backend)] = jax.jit(
                    lambda a, b, tr, tm, be=backend, ip=interp, e=ec, i=ic:
                    D.mcma_dispatch_sharded(
                        mesh, a, b, exact_fn_p, (wi, wo), w1, b1, w2, b2,
                        exact_cap=e, invoke_cap=i, backend=be,
                        block_t=block_t, interpret=ip,
                        tier=tr, tier_margins=tm))
        return fns[(idx, backend)](x, lg, tier, margins)

    for mi, mix in enumerate(mixes):
        p = np.asarray(mix, float) / sum(mix)
        tier = jnp.asarray(
            np.random.default_rng(100 + mi).choice(nt, t, p=p), jnp.int32)
        for idx, pt in enumerate(points):
            yx, sx = run_point(idx, tier, "xla")
            yp, sp = run_point(idx, tier, "pallas")
            err = float(np.abs(np.asarray(yp) - np.asarray(yx)).max())
            assert err < 1e-4, \
                f"pallas-vs-xla divergence at point {pt} mix {mix}: {err}"
            tc = np.asarray(sx["tier_counts"], float)
            td = np.asarray(sx["tier_dispatched"], float)
            trows = tc.sum(-1)
            assert (trows > 0).all(), (mix, trows)
            served = td[:, 1:].sum(-1) / trows
            # the QoS contract this PR ships: a looser bound buys strictly
            # more SERVED invocation than a tighter one in the same batch
            assert served[-1] > served[0], \
                f"loose tier must out-invoke tight at point {pt} mix " \
                f"{mix}: served={served}"
            row = {
                "T": t, "n_approx": n, "d_model": d, "backend": "both",
                "block_t": block_t, "interpret": on_cpu,
                "devices": devices, "mode": "qos",
                "op_index": idx,
                "op_exact_frac": pt.exact_frac,
                "op_invoke_frac": pt.invoke_frac,
                "op_invoke_fracs": "/".join(str(f) for f in
                                            pt.class_fracs(n)),
                "tier_mix": ":".join(str(m) for m in mix),
                "invocation": round(float(sx["invocation"]), 4),
                "exact_frac": round(float(sx["exact_frac"]), 4),
                "dropped": int(sx["dropped"]),
                "max_abs_err_vs_xla": round(err, 7),
            }
            for k in range(nt):
                row[f"tier{k}_bound"] = bounds[k]
                row[f"tier{k}_margin"] = round(float(margins[k]), 3)
                row[f"tier{k}_rows"] = int(trows[k])
                row[f"tier{k}_served_inv"] = round(float(served[k]), 4)
                row[f"tier{k}_dropped"] = int(
                    (tc[k] - td[k]).sum())
            rows.append(row)
            print(f"qos x{devices} mix={row['tier_mix']:5s} point={idx} "
                  f"served_inv per tier="
                  f"{[round(float(s), 3) for s in served]}", flush=True)
    # margins and tier mixes are traced inputs: every mix above reused
    # ONE compiled program per (rung, backend)
    for f in fns.values():
        assert_zero_retrace(f, "a tier-mix change")


def _library_leg(rows, *, quick, devices=1):
    """Approximator-library residency microbench: a 16-member library
    with 4 resident slots serves a phase-shifting skewed demand mix.
    The residency-tuned arm (runtime/autotune.ResidencyController) and a
    static-first-n baseline run the SAME compiled program at the SAME
    capacities (same drop budget) — the tuned arm must serve strictly
    more approximator rows once demand shifts onto off-set classes.
    Pallas is gated against the XLA oracle at every visited residency
    set, and the whole trajectory must cost ZERO retraces (a swap is a
    new traced index vector)."""
    from repro.kernels import ops
    from repro.runtime.autotune import ResidencyController
    from repro.runtime.options import LibrarySpec
    from repro.sharding.rules import shard_capacity

    lib, n_res = 16, 4
    t = 256 if quick else 1024
    d, d_h, d_ff, block_t = (128, 32, 256, 64) if quick \
        else (256, 64, 1024, 128)
    on_cpu = jax.default_backend() != "tpu"
    key = jax.random.PRNGKey(29)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    w1 = jax.random.normal(ks[1], (lib, d, d_h)) * 0.2
    b1 = jnp.zeros((lib, d_h))
    w2 = jax.random.normal(ks[2], (lib, d_h, d)) * 0.2
    b2 = jnp.zeros((lib, d))
    wi = jax.random.normal(ks[3], (d, d_ff)) * 0.1
    wo = jax.random.normal(ks[4], (d_ff, d)) * 0.1
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])
    W = ops.prepad_switched_weights(w1, b1, w2, b2)   # full library, once

    mesh = make_mesh((devices,), ("data",)) if devices > 1 else None
    tl = t // devices
    ec = shard_capacity(tl, 0.5)
    ic = shard_capacity(tl, 0.3)                      # per resident slot

    fns = {}
    for backend in ("xla", "pallas"):
        interp = on_cpu and backend == "pallas"
        if mesh is None:
            fns[backend] = jax.jit(
                lambda xx, lg, rv, be=backend, ip=interp:
                D.mcma_dispatch(xx, lg, exact_fn, *W, exact_cap=ec,
                                invoke_cap=ic, backend=be, block_t=block_t,
                                interpret=ip, weights_prepadded=True,
                                residency=rv))
        else:
            fns[backend] = jax.jit(
                lambda xx, lg, rv, be=backend, ip=interp:
                D.mcma_dispatch_sharded(
                    mesh, xx, lg, exact_fn_p, (wi, wo), *W, exact_cap=ec,
                    invoke_cap=ic, backend=be, block_t=block_t,
                    interpret=ip, weights_prepadded=True, residency=rv))

    spec = LibrarySpec(library_size=lib, n_resident=n_res,
                       observe_window=2, cooldown=2, ema=0.5)
    ctrl = ResidencyController(spec)
    static = jnp.arange(n_res, dtype=jnp.int32)

    # three demand phases: hot class starts resident, then demand shifts
    # onto two off-set classes — the static arm folds the hot traffic
    # onto the exact path, the tuned arm swaps the hot weights in
    phases = [(1, 8), (10, 12), (14, 12)] if quick \
        else [(1, 10), (10, 20), (14, 20)]
    tuned_acc = np.zeros(2)                           # served approx, dropped
    static_acc = np.zeros(2)
    tick = 0
    tick_ms = []                                      # (post_swap, ms)
    for hot_cls, ticks in phases:
        for _ in range(ticks):
            lg = _skewed_logits(jax.random.fold_in(key, tick), t, lib,
                                hot_cls + 1, 0.6)
            resv = jnp.asarray(ctrl.residency, jnp.int32)
            swaps_before = len(ctrl.history)
            t0 = time.perf_counter()
            yx, sx = fns["xla"](x, lg, resv)
            jax.block_until_ready(yx)
            ms = (time.perf_counter() - t0) * 1e3
            yp, sp = fns["pallas"](x, lg, resv)
            err = float(np.abs(np.asarray(yp) - np.asarray(yx)).max())
            assert err < 1e-4, \
                f"pallas-vs-xla divergence at residency " \
                f"{ctrl.residency}: {err}"
            # static baseline: same program, the start residency (free —
            # residency is a traced input, no second compile)
            ss = sx if tuple(np.asarray(resv)) == tuple(range(n_res)) \
                else fns["xla"](x, lg, static)[1]
            tuned_acc += (float(np.asarray(sx["dispatched"])[1:].sum()),
                          float(sx["dropped"]))
            static_acc += (float(np.asarray(ss["dispatched"])[1:].sum()),
                           float(ss["dropped"]))
            ctrl.observe(jax.tree.map(np.asarray, sx))
            post_swap = len(ctrl.history) > swaps_before
            tick_ms.append((post_swap, ms))
            rows.append({
                "T": t, "n_approx": n_res, "d_model": d, "backend": "both",
                "block_t": block_t, "interpret": on_cpu,
                "devices": devices, "mode": "library",
                "tick": tick, "library_size": lib,
                "residency": "/".join(str(c) for c in
                                      np.asarray(resv).tolist()),
                "swap_count": len(ctrl.history),
                "ms_per_call": round(ms, 3),
                "invocation": round(float(sx["invocation"]), 4),
                "exact_frac": round(float(sx["exact_frac"]), 4),
                "dropped": int(sx["dropped"]),
                "served_invocation": round(
                    float(np.asarray(sx["dispatched"])[1:].sum())
                    / max(float(np.asarray(sx["class_counts"]).sum()), 1),
                    4),
                "off_set_exact_rows": int(sx["off_set_exact_rows"]),
                "static_served_invocation": round(
                    float(np.asarray(ss["dispatched"])[1:].sum())
                    / max(float(np.asarray(ss["class_counts"]).sum()), 1),
                    4),
                "max_abs_err_vs_xla": round(err, 7),
            })
            tick += 1

    # swap economics for the CSV/summary: a swap is a traced-index
    # update, so post-swap ticks must not pay a recompile
    steady = [m for p, m in tick_ms[1:] if not p]
    after = [tick_ms[i + 1][1] for i, (p, _) in enumerate(tick_ms[:-1])
             if p]
    swap_cost = (float(np.median(after)) - float(np.median(steady))) \
        if after and steady else 0.0
    swap_rate = len(ctrl.history) / tick
    rows.append({
        "T": t, "n_approx": n_res, "d_model": d, "backend": "both",
        "block_t": block_t, "interpret": on_cpu, "devices": devices,
        "mode": "library-summary", "library_size": lib, "tick": tick,
        "swap_count": len(ctrl.history),
        "swap_rate": round(swap_rate, 4),
        "swap_cost_ms": round(swap_cost, 3),
        "served_invocation": round(tuned_acc[0] / (tick * t), 4),
        "static_served_invocation": round(static_acc[0] / (tick * t), 4),
        "dropped": int(tuned_acc[1]),
        "residency": "/".join(str(c) for c in ctrl.residency),
    })
    print(f"library x{devices}: {len(ctrl.history)} swaps over {tick} "
          f"ticks (rate {swap_rate:.3f}, post-swap cost "
          f"{swap_cost:+.2f} ms), served approx rows tuned "
          f"{tuned_acc[0]:.0f} vs static {static_acc[0]:.0f} "
          f"(dropped {tuned_acc[1]:.0f} vs {static_acc[1]:.0f})",
          flush=True)
    # acceptance gates: the tuned arm must win strictly, the demand shift
    # must actually stress the static set, and NOTHING may have retraced
    assert len(ctrl.history) >= 2, \
        "demand phases failed to trigger residency swaps"
    assert tuned_acc[0] > static_acc[0], \
        "residency tuning must serve strictly more approximator rows " \
        "than the static resident set at the same capacities"
    for backend, f in fns.items():
        assert_zero_retrace(f, f"{backend}: a residency swap")


def _decode_tick_leg(rows, *, quick):
    """Full decode tick, route_scope=layer vs tick, oracle-gated."""
    import dataclasses
    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.runtime import steps as S

    on_cpu = jax.default_backend() != "tpu"
    n_layers, batch, iters = (4, 64, 3) if quick else (8, 128, 10)
    base = smoke_config(get_config("internlm2-1.8b"))
    base = dataclasses.replace(base, n_layers=n_layers)

    def cfg_with(scope, backend):
        return dataclasses.replace(base, approx=dataclasses.replace(
            base.approx, enable=True, backend=backend,
            interpret=on_cpu and backend == "pallas", block_t=32,
            route_scope=scope))

    params = M.init_model(jax.random.PRNGKey(0), cfg_with("layer", "xla"))
    cache = M.init_cache(base, batch, 64)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, base.vocab, (batch, 1)),
        jnp.int32)
    mask = jnp.ones((batch,), bool)

    sorts = {}
    for scope in ("layer", "tick"):
        outs = {}
        for backend in ("xla", "pallas"):
            cfg = cfg_with(scope, backend)
            step = jax.jit(S.make_decode_step(cfg, with_stats=True))
            lg, _, m = step(params, cache, toks, mask)
            jax.block_until_ready(lg)                # compile off the clock
            t0 = time.perf_counter()
            for _ in range(iters):
                lg, _, m = step(params, cache, toks, mask)
            jax.block_until_ready(lg)
            ms = (time.perf_counter() - t0) / iters * 1e3
            outs[backend] = np.asarray(lg)
            jaxpr = jax.make_jaxpr(S.make_decode_step(cfg, with_stats=True))(
                params, cache, toks, mask).jaxpr
            n_sorts = count_dynamic_ops(jaxpr, {"sort"})
            n_scatter = count_dynamic_ops(
                jaxpr, {"scatter", "scatter-add"})
            sorts[(scope, backend)] = n_sorts
            rows.append({
                "T": batch, "n_approx": base.approx.n_approx,
                "d_model": base.d_model, "backend": backend,
                "block_t": 32, "interpret": on_cpu and backend == "pallas",
                "devices": 1, "mode": "decode-tick",
                "route_scope": scope, "layers": n_layers,
                "ms_per_tick": round(ms, 3),
                "sorts_per_tick": n_sorts,
                "scatters_per_tick": n_scatter,
                "invocation": round(float(m["invocation"]), 4),
                "exact_frac": round(float(m["exact_frac"]), 4),
            })
            print(f"decode-tick L={n_layers} B={batch} scope={scope:5s} "
                  f"{backend:6s} {ms:9.2f} ms/tick sorts={n_sorts} "
                  f"scatters={n_scatter}", flush=True)
        # oracle gate at this scope, same as the other legs
        err = float(np.abs(outs["pallas"] - outs["xla"]).max())
        for row in rows[-2:]:
            row["max_abs_err_vs_xla"] = round(err, 7) \
                if row["backend"] == "pallas" else 0.0
        assert err < 1e-4, f"decode-tick divergence at scope={scope}: {err}"
    # the leg's acceptance gate: one class-sort per tick, not one per
    # layer.  Only the Pallas executor sorts (the plan builds the sort
    # for it; the XLA oracle re-derives per-class slots from cls/rank and
    # honestly records 0 at both scopes — no dead argsorts in the CSV).
    assert sorts[("layer", "pallas")] == n_layers, sorts
    assert sorts[("tick", "pallas")] == 1, sorts
    assert sorts[("tick", "xla")] <= sorts[("layer", "xla")], sorts


def _backend_sweep_leg(rows, *, quick, iters, devices):
    """Fused vs unfused Pallas vs XLA at tick scope, op-count audited.

    One DispatchPlan per backend drives an L-layer ``lax.scan`` of
    ``execute_dispatch`` (distinct weights per layer; the approximators
    map d -> d so layer outputs chain through the next layer), timed as
    ms_per_tick.  Each backend's tick jaxpr is audited with
    ``repro.analysis.opcount.activation_moves`` — STANDALONE
    activation-sized (rank >= 2) gathers/scatters per layer, pallas
    kernel bodies excluded.  Gates per shape:

      * fused runs <= 1 standalone gather and <= 1 scatter per layer
        (the exact-path capacity buffers) and strictly fewer of both
        than unfused pallas (which pays the class-sort gather/scatter
        legs per layer);
      * fused is BITWISE equal to unfused pallas and < 1e-4 vs the XLA
        oracle — at BOTH visited operating points (uniform caps with a
        row mask + tiered margins, then asymmetric per-class caps);
      * moving to the second operating point retraces nothing (mask,
        tier, margins are traced plan inputs);
      * on CPU (interpret mode) fused must not be slower than unfused —
        the fused kernel does strictly less XLA-level work there, so a
        regression means the fusion itself broke.
    """
    on_cpu = jax.default_backend() != "tpu"
    layers = 4
    if quick:
        shapes = [(256, 2), (512, 4)]
        d, d_h, d_ff, block_t = 128, 32, 256, 64
    else:
        shapes = [(1024, 4), (2048, 8) if on_cpu else (4096, 8)]
        d, d_h, d_ff, block_t = 512, 64, 2048, 128
    iters = iters or (3 if quick else 10)
    margins = jnp.asarray([0.5, 0.0, -0.5], jnp.float32)

    for t, n in shapes:
        key = jax.random.PRNGKey(t * 131 + n)
        x, logits, (w1, b1, w2, b2), (wi, wo) = _make_case(
            key, t, n, d, d_h, d_ff)
        exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
        # L distinct approximator banks: scaled copies keep layer outputs
        # bounded while making every layer a real weight switch
        stacked = jax.tree.map(
            lambda a: jnp.stack([a * (0.7 + 0.1 * i)
                                 for i in range(layers)]),
            (w1, b1, w2, b2))
        tier = jnp.arange(t, dtype=jnp.int32) % 3
        mask = jnp.arange(t) % 16 != 0
        # two operating points: uniform caps, then asymmetric per-class
        cap_points = [
            (max(t // 2, 1), max(int(t * 0.4), 1)),
            (max(t // 2, 1), tuple(max(t // (4 + 2 * c), block_t)
                                   for c in range(n))),
        ]
        per_tick, outs = {}, {be: [] for be in D.DISPATCH_BACKENDS}
        for backend in D.DISPATCH_BACKENDS:
            interp = on_cpu and backend in D.PALLAS_BACKENDS
            for pt_i, (ec, ic) in enumerate(cap_points):
                plan_fn = jax.jit(
                    lambda lg, tr, mg, mk, be=backend, e=ec, i=ic:
                    D.make_dispatch_plan(
                        lg, mk, exact_cap=e, invoke_cap=i, backend=be,
                        block_t=block_t, tier=tr, tier_margins=mg))

                def tick(plan, xx, ip=interp):
                    def layer(h, ws):
                        lw1, lb1, lw2, lb2 = ws
                        return D.execute_dispatch(
                            plan, h, exact_fn, lw1, lb1, lw2, lb2,
                            interpret=ip), None
                    return jax.lax.scan(layer, xx, stacked)[0]

                tick_fn = jax.jit(tick)
                plan = plan_fn(logits, tier, margins, mask)
                y = tick_fn(plan, x)
                jax.block_until_ready(y)         # compile off the clock
                t0 = time.perf_counter()
                for _ in range(iters):
                    y = tick_fn(plan, x)
                jax.block_until_ready(y)
                ms = (time.perf_counter() - t0) / iters * 1e3
                outs[backend].append(np.asarray(y))
                if pt_i == 0:
                    g, s = activation_moves(jax.make_jaxpr(tick)(plan, x))
                    assert g % layers == 0 and s % layers == 0, (g, s)
                    gl, sl = g // layers, s // layers
                    per_tick[backend] = (ms, gl, sl)
                    stats = D.plan_invoke_stats(plan)
                    rows.append({
                        "T": t, "n_approx": n, "d_model": d,
                        "backend": backend, "block_t": block_t,
                        "interpret": interp, "devices": 1,
                        "mode": "backend-sweep", "layers": layers,
                        "ms_per_tick": round(ms, 3),
                        "gathers_per_layer": gl,
                        "scatters_per_layer": sl,
                        "invocation": round(float(stats["invocation"]), 4),
                        "exact_frac": round(float(stats["exact_frac"]), 4),
                        "dropped": int(stats["dropped"]),
                    })
                    print(f"backend-sweep T={t:6d} n={n} L={layers} "
                          f"{backend:12s} {ms:9.2f} ms/tick "
                          f"gathers/layer={gl} scatters/layer={sl}",
                          flush=True)
                else:
                    # operating-point switch: new caps = new shapes = one
                    # fresh compile, but mask/tier/margins stay traced —
                    # replaying the FIRST point's fns must not retrace
                    p0 = plan_fn_prev(logits, tier, margins, ~mask)
                    jax.block_until_ready(tick_fn_prev(p0, x))
                    assert_zero_retrace(
                        plan_fn_prev, f"{backend}: a mask/tier change")
                    assert_zero_retrace(
                        tick_fn_prev, f"{backend}: a replanned tick")
                plan_fn_prev, tick_fn_prev = plan_fn, tick_fn

        # divergence gates at every visited operating point
        for pt_i in range(len(cap_points)):
            err_f = float(np.abs(outs["pallas_fused"][pt_i] -
                                 outs["xla"][pt_i]).max())
            assert err_f < 1e-4, \
                f"fused-vs-xla divergence at T={t} point {pt_i}: {err_f}"
            assert np.array_equal(outs["pallas_fused"][pt_i],
                                  outs["pallas"][pt_i]), \
                f"fused != unfused pallas bitwise at T={t} point {pt_i}"
        for r in rows:
            if r.get("mode") == "backend-sweep" and r["T"] == t \
                    and r["backend"] != "xla":
                r["max_abs_err_vs_xla"] = round(err_f, 7)

        # op-count gates: the fused kernel leaves at most the exact-path
        # capacity buffers (1 gather + 1 scatter) standalone per layer;
        # unfused pallas additionally pays the class-sort legs
        _, gf, sf = per_tick["pallas_fused"]
        _, gu, su = per_tick["pallas"]
        assert gf <= 1 and sf <= 1, \
            f"fused backend runs {gf} gathers/{sf} scatters per layer"
        assert gf < gu and sf < su, \
            f"fusion audit: fused ({gf},{sf}) vs unfused ({gu},{su})"
        if on_cpu:
            ms_f, ms_u = per_tick["pallas_fused"][0], per_tick["pallas"][0]
            assert ms_f <= ms_u * 1.10, \
                (f"fused slower than unfused in interpret mode at T={t}: "
                 f"{ms_f:.2f} vs {ms_u:.2f} ms/tick")
        for r in rows:
            if r.get("mode") == "backend-sweep" and r["T"] == t \
                    and r["backend"] == "pallas_fused":
                r["speedup_vs_unfused"] = round(
                    per_tick["pallas"][0] / per_tick["pallas_fused"][0], 3)

    if devices > 1:
        _backend_sweep_sharded(rows, quick=quick, iters=iters,
                               devices=devices)


def _backend_sweep_sharded(rows, *, quick, iters, devices):
    """One shape through ``mcma_dispatch_sharded`` per backend on an
    N-way mesh — the fused kernel inside shard_map, gated bitwise against
    unfused pallas and < 1e-4 against the XLA oracle."""
    on_cpu = jax.default_backend() != "tpu"
    t, n = (512, 4) if quick else (2048, 4)
    d, d_h, d_ff, block_t = (128, 32, 256, 64) if quick \
        else (512, 64, 2048, 128)
    assert t % devices == 0, (t, devices)
    tl = t // devices
    ec_l, ic_l = max(tl // 2, 1), max(int(tl * 0.4), 1)
    key = jax.random.PRNGKey(t * 131 + n + 1)
    x, logits, (w1, b1, w2, b2), (wi, wo) = _make_case(
        key, t, n, d, d_h, d_ff)
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])
    mesh = make_mesh((devices,), ("data",))
    outs = {}
    for backend in D.DISPATCH_BACKENDS:
        interp = on_cpu and backend in D.PALLAS_BACKENDS
        fn = jax.jit(lambda xx, lg, be=backend, ip=interp:
                     D.mcma_dispatch_sharded(
                         mesh, xx, lg, exact_fn_p, (wi, wo),
                         w1, b1, w2, b2, exact_cap=ec_l,
                         invoke_cap=ic_l, backend=be,
                         block_t=block_t, interpret=ip))
        ms, stats = _time(fn, x, logits, iters=iters)
        outs[backend] = np.asarray(fn(x, logits)[0])
        _record(rows, t=t, n=n, d=d, backend=backend, block_t=block_t,
                interpret=interp, ms=ms, stats=stats, devices=devices,
                mode="backend-sweep-sharded")
        print(f"  (sharded sweep x{devices})", flush=True)
    err = float(np.abs(outs["pallas_fused"] - outs["xla"]).max())
    assert err < 1e-4, f"sharded fused-vs-xla divergence: {err}"
    assert np.array_equal(outs["pallas_fused"], outs["pallas"]), \
        "sharded fused != unfused pallas bitwise"
    for r in rows[-2:]:
        r["max_abs_err_vs_xla"] = round(err, 7)


def main(quick: bool = False, iters: int | None = None, devices: int = 1,
         autotune: bool = False, decode_tick: bool = False,
         qos: bool = False, library: bool = False,
         backend_sweep: bool = False):
    os.makedirs(OUT, exist_ok=True)
    on_cpu = jax.default_backend() != "tpu"
    if devices > 1 and len(jax.devices()) < devices:
        raise SystemExit(
            f"--devices {devices} needs {devices} jax devices but only "
            f"{len(jax.devices())} exist; run via `python -m "
            f"benchmarks.bench_dispatch` (which forces virtual CPU devices) "
            f"or set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{devices}")
    if quick:
        shapes = [(256, 2), (512, 4)]
        d, d_h, d_ff, block_t = 128, 32, 256, 64
        iters = iters or 3
    else:
        shapes = [(1024, 2), (4096, 4), (4096, 8), (16384, 4)]
        d, d_h, d_ff, block_t = 512, 64, 2048, 256
        iters = iters or 10
        if on_cpu:  # interpreter-mode Pallas: keep CPU runs bounded
            shapes = [s for s in shapes if s[0] <= 4096]

    rows = []
    for t, n in shapes:
        key = jax.random.PRNGKey(t * 31 + n)
        x, logits, (w1, b1, w2, b2), (wi, wo) = _make_case(
            key, t, n, d, d_h, d_ff)
        exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
        exact_cap, invoke_cap = max(t // 2, 1), max(int(t * 0.4), 1)
        outs = {}
        for backend in ("xla", "pallas"):
            interp = on_cpu and backend == "pallas"
            fn = jax.jit(lambda xx, lg, be=backend, ip=interp:
                         D.mcma_dispatch(
                             xx, lg, exact_fn, w1, b1, w2, b2,
                             exact_cap=exact_cap, invoke_cap=invoke_cap,
                             backend=be, block_t=block_t, interpret=ip))
            ms, stats = _time(fn, x, logits, iters=iters)
            y, _ = fn(x, logits)
            outs[backend] = np.asarray(y)
            _record(rows, t=t, n=n, d=d, backend=backend, block_t=block_t,
                    interpret=interp, ms=ms, stats=stats, devices=1,
                    mode="single")
        _check_oracle(rows, outs, t, n)

        if devices > 1:
            assert t % devices == 0, (t, devices)
            tl = t // devices
            ec_l, ic_l = max(tl // 2, 1), max(int(tl * 0.4), 1)
            mesh = make_mesh((devices,), ("data",))
            exact_fn_p = lambda ep, xb: jnp.dot(
                jax.nn.silu(jnp.dot(xb, ep[0])), ep[1])
            outs_sh = {}
            for backend in ("xla", "pallas"):
                interp = on_cpu and backend == "pallas"
                # global sharded call: all shards dispatch concurrently,
                # invoke_stats psum-reduced to global totals
                fn = jax.jit(lambda xx, lg, be=backend, ip=interp:
                             D.mcma_dispatch_sharded(
                                 mesh, xx, lg, exact_fn_p, (wi, wo),
                                 w1, b1, w2, b2, exact_cap=ec_l,
                                 invoke_cap=ic_l, backend=be,
                                 block_t=block_t, interpret=ip))
                ms, stats = _time(fn, x, logits, iters=iters)
                y, _ = fn(x, logits)
                outs_sh[backend] = np.asarray(y)
                _record(rows, t=t, n=n, d=d, backend=backend,
                        block_t=block_t, interpret=interp, ms=ms,
                        stats=stats, devices=devices, mode="sharded")
            _check_oracle(rows, outs_sh, t, n)
            # shard-local baseline: one shard's rows on one device — the
            # per-shard cost the sharded mode amortizes across devices
            outs_loc = {}
            for backend in ("xla", "pallas"):
                interp = on_cpu and backend == "pallas"
                fn = jax.jit(lambda xx, lg, be=backend, ip=interp:
                             D.mcma_dispatch(
                                 xx, lg, exact_fn, w1, b1, w2, b2,
                                 exact_cap=ec_l, invoke_cap=ic_l,
                                 backend=be, block_t=block_t, interpret=ip))
                ms, stats = _time(fn, x[:tl], logits[:tl], iters=iters)
                outs_loc[backend] = np.asarray(fn(x[:tl], logits[:tl])[0])
                _record(rows, t=tl, n=n, d=d, backend=backend,
                        block_t=block_t, interpret=interp, ms=ms,
                        stats=stats, devices=1, mode="shard-local")
            _check_oracle(rows, outs_loc, tl, n)

    if autotune:
        _autotune_leg(rows, quick=quick, devices=devices)
    if qos:
        _qos_leg(rows, quick=quick, devices=devices)
    if library:
        _library_leg(rows, quick=quick, devices=devices)
    if decode_tick:
        _decode_tick_leg(rows, quick=quick)
    if backend_sweep:
        _backend_sweep_leg(rows, quick=quick, iters=iters, devices=devices)

    # column union across modes (the autotune rows add trajectory columns)
    fields = list(rows[0].keys())
    for r in rows:
        fields += [k for k in r if k not in fields]
    with open(os.path.join(OUT, "dispatch.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fields, restval="")
        w.writeheader()
        w.writerows(rows)
    print(f"wrote {os.path.join(OUT, 'dispatch.csv')} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the dispatch over an N-way data mesh "
                         "(forces N virtual CPU devices when run as main)")
    ap.add_argument("--autotune", action="store_true",
                    help="add the capacity-autotuning trajectory leg "
                         "(controller over a skewed phase-shifting mix; "
                         "pallas-vs-xla gated at every operating point)")
    ap.add_argument("--decode-tick", action="store_true",
                    help="add the tick-level dispatch-planning leg: a full "
                         "L-layer decode tick at route_scope=layer vs tick "
                         "(per-tick wall + dynamic sort/scatter op counts; "
                         "asserts 1 class-sort per tick under tick scope "
                         "and pallas==xla at both scopes)")
    ap.add_argument("--library", action="store_true",
                    help="add the approximator-library residency leg: a "
                         "16-member library with 4 resident slots over a "
                         "phase-shifting skewed mix; the controller-tuned "
                         "residency must serve strictly more approximator "
                         "rows than the static first-4 set at the same "
                         "capacities, pallas==xla at every visited "
                         "residency set, zero retraces across swaps")
    ap.add_argument("--qos", action="store_true",
                    help="add the per-request QoS tier-mix sweep: mixed "
                         "error-bound batches at several operating points "
                         "(incl. an asymmetric per-class-capacity rung), "
                         "pallas-vs-xla gated per mix; asserts loose-bound "
                         "rows serve strictly more invocation than "
                         "tight-bound rows at every visited point")
    ap.add_argument("--backend-sweep", action="store_true",
                    help="add the fused-kernel sweep: fused vs unfused "
                         "pallas vs xla over an L-layer tick per shape "
                         "(ms_per_tick + standalone activation gather/"
                         "scatter counts per layer from a jaxpr audit); "
                         "asserts the fused backend runs <=1 of each per "
                         "layer, matches unfused pallas BITWISE and the "
                         "xla oracle <1e-4 at every visited operating "
                         "point, and is no slower than unfused in "
                         "interpret mode (with --devices N also through "
                         "the sharded engine)")
    args = ap.parse_args()
    if args.devices > 1 and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # must land before jax initializes its backend (first device use)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}").strip()
    main(quick=args.quick, iters=args.iters, devices=args.devices,
         autotune=args.autotune, decode_tick=args.decode_tick,
         qos=args.qos, library=args.library,
         backend_sweep=args.backend_sweep)
