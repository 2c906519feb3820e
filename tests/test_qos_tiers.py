"""Per-request QoS tiers: an error bound threaded from ``Request`` through
routing, the ``DispatchPlan``, and the per-class-capacity autotune grid.

Pins, per the PR's acceptance criteria:
  * a UNIFORM default-tier batch is bit-for-bit identical to the
    margin-free engine — both backends, with and without ``row_mask``,
    at layer and tick scope (the tier plumbing is a pure widening);
  * a MIXED-tier batch is pallas == xla bit-for-bit on 1 device and on
    the 8-virtual-device (data, model) mesh (subprocess + in-process
    CI-leg variants), and the per-tier psum'd stats equal the
    single-device split exactly;
  * the per-tier stat split sums back to the totals, and a looser bound
    (more negative margin) buys strictly more served invocation than a
    tighter one in the same batch;
  * asymmetric per-class capacities (``invoke_cap`` tuples /
    ``OperatingPoint.invoke_fracs``) clamp each class at its own budget,
    and ``ladder_from_counts`` derives them from served class-count
    quantiles of a skewed mix;
  * ``DecodeServer`` validates ``Request.error_bound`` against the tier
    table (anchored on the apps-registry quality bound) at submit time —
    out-of-range fails loudly — and reports per-tier served invocation +
    dropped_frac in the drain summary.
"""
import dataclasses
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jit_cache import assert_zero_retrace
from repro.configs.registry import get_config, smoke_config
from repro.models import model as M
from repro.runtime import autotune as AT
from repro.runtime import dispatch as D
from repro.runtime import steps as S

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}

LEGACY_KEYS = ("class_counts", "dispatched", "dropped", "exact_frac",
               "invocation", "executed_rows", "padding_rows")


def _run(script: str) -> dict:
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.split("RESULT")[1])


def _mk_case(key, t, n, d, d_h):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (d, n + 1)) * 0.5
    w1 = jax.random.normal(ks[2], (n, d, d_h)) * 0.2
    b1 = jax.random.normal(ks[3], (n, d_h)) * 0.1
    w2 = jax.random.normal(ks[4], (n, d_h, d)) * 0.2
    b2 = jax.random.normal(ks[5], (n, d)) * 0.1
    wi = jax.random.normal(jax.random.fold_in(key, 7), (d, 2 * d)) * 0.1
    wo = jax.random.normal(jax.random.fold_in(key, 8), (2 * d, d)) * 0.1
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    return x, x @ router, (w1, b1, w2, b2), exact_fn


def _approx_cfg(**over):
    cfg = smoke_config(get_config("internlm2-1.8b"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, **over))


def _mixed_tier(t, nt=3, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, nt, t),
                       jnp.int32)


MARGINS = jnp.asarray([3.0, 0.0, -3.0])          # tight / base / loose


# ---------------------------------------------------------------------------
# uniform default tier == the margin-free engine, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("with_mask", [False, True])
def test_uniform_tier_engine_bitexact(backend, with_mask):
    """All rows on a zero-margin tier: output AND every legacy stat must
    be bit-identical to the engine without the tier arguments — even with
    nonzero margins parked at the unused tier indices."""
    t, n, d, d_h, block = 96, 3, 48, 16, 32
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(11), t, n, d, d_h)
    rm = (jnp.arange(t) % 5 != 0) if with_mask else None
    kw = dict(exact_cap=t // 2, invoke_cap=max(int(t * 0.3), 1),
              backend=backend, block_t=block, interpret=backend == "pallas")
    y0, s0 = D.mcma_dispatch(x, logits, exact_fn, *w, row_mask=rm, **kw)
    # every row on the BASE tier (index 1: margin 0.0) — the nonzero
    # margins parked at the unused tier indices must not matter
    y1, s1 = D.mcma_dispatch(x, logits, exact_fn, *w, row_mask=rm,
                             tier=jnp.ones((t,), jnp.int32),
                             tier_margins=MARGINS, **kw)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    for k in LEGACY_KEYS:
        np.testing.assert_array_equal(np.asarray(s0[k]), np.asarray(s1[k]))
    # the base tier carries everything; the unused tiers are exactly empty
    np.testing.assert_array_equal(np.asarray(s1["tier_counts"])[1],
                                  np.asarray(s1["class_counts"]))
    assert np.asarray(s1["tier_counts"])[0].sum() == 0
    assert np.asarray(s1["tier_counts"])[2].sum() == 0


@pytest.mark.parametrize("route_scope", ["layer", "tick"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_uniform_tier_decode_step_bitexact(route_scope, backend):
    """The decode step with a uniform default-tier vector reproduces
    today's engine exactly, at both routing scopes and both backends."""
    kw = {} if backend == "xla" else dict(interpret=True, block_t=16)
    cfg = _approx_cfg(backend=backend, route_scope=route_scope, n_tiers=3,
                      **kw)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    b = 4
    cache = M.init_cache(cfg, b, 32)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    mask = jnp.asarray([True, True, False, True])
    lg0, _, m0 = M.decode(cfg, params, cache, toks, serve=True,
                          collect_metrics=True, row_mask=mask)
    lg1, _, m1 = M.decode(cfg, params, cache, toks, serve=True,
                          collect_metrics=True, row_mask=mask,
                          tier=jnp.ones((b,), jnp.int32),
                          tier_margins=MARGINS)
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))
    np.testing.assert_array_equal(np.asarray(m0["class_counts"]),
                                  np.asarray(m1["class_counts"]))
    np.testing.assert_array_equal(np.asarray(m0["dispatched"]),
                                  np.asarray(m1["dispatched"]))


# ---------------------------------------------------------------------------
# mixed tiers: backend equivalence + the per-tier stat split
# ---------------------------------------------------------------------------

def test_mixed_tier_engine_pallas_matches_xla():
    t, n, d, d_h = 128, 3, 48, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(3), t, n, d, d_h)
    tier = _mixed_tier(t)
    outs, stats = {}, {}
    for backend in ("xla", "pallas"):
        y, s = D.mcma_dispatch(
            x, logits, exact_fn, *w, exact_cap=t // 2,
            invoke_cap=max(int(t * 0.3), 1), backend=backend, block_t=32,
            interpret=backend == "pallas", tier=tier, tier_margins=MARGINS)
        outs[backend], stats[backend] = np.asarray(y), \
            jax.tree.map(np.asarray, s)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    for k in ("tier_counts", "tier_dispatched", "class_counts"):
        np.testing.assert_array_equal(stats["pallas"][k], stats["xla"][k])


def test_tier_split_sums_to_totals_and_is_monotone():
    """The per-tier matrices partition the totals exactly, and the loose
    tier serves strictly more invocation than the tight one."""
    t, n, d, d_h = 256, 3, 48, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(7), t, n, d, d_h)
    tier = _mixed_tier(t)
    rm = jnp.arange(t) % 7 != 0
    _, s = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=t // 2,
                           invoke_cap=max(int(t * 0.25), 1), backend="xla",
                           row_mask=rm, tier=tier, tier_margins=MARGINS)
    s = jax.tree.map(np.asarray, s)
    np.testing.assert_array_equal(s["tier_counts"].sum(0),
                                  s["class_counts"])
    np.testing.assert_array_equal(s["tier_dispatched"].sum(0),
                                  s["dispatched"])
    assert s["tier_dropped"].sum() == s["dropped"]
    served = s["tier_served_invocation"]
    assert served[2] > served[0], served
    # routed invocation is monotone across ALL tiers (margins 3 > 0 > -3)
    routed = s["tier_counts"][:, 1:].sum(-1) / s["tier_counts"].sum(-1)
    assert routed[0] < routed[1] < routed[2], routed


@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_mixed_tier_decode_pallas_matches_xla(route_scope):
    b = 6
    tier = jnp.asarray([0, 1, 2, 2, 1, 0], jnp.int32)
    mask = jnp.asarray([True] * 5 + [False])
    params = M.init_model(jax.random.PRNGKey(0), _approx_cfg())
    outs, stats = {}, {}
    for be, kw in (("xla", {}),
                   ("pallas", dict(interpret=True, block_t=16))):
        cfg = _approx_cfg(backend=be, route_scope=route_scope, n_tiers=3,
                          **kw)
        cache = M.init_cache(cfg, b, 32)
        toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
        lg, _, m = M.decode(cfg, params, cache, toks, serve=True,
                            collect_metrics=True, row_mask=mask,
                            tier=tier, tier_margins=MARGINS)
        outs[be], stats[be] = np.asarray(lg), jax.tree.map(np.asarray, m)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(stats["pallas"]["tier_counts"],
                                  stats["xla"]["tier_counts"])
    # the masked slot (tier 0) is excluded from every tier's counts
    assert stats["xla"]["tier_counts"].sum() == 5


def test_tier_without_margins_or_n_tiers_fails_loudly():
    """A tier vector without a margins vector (or explicit n_tiers) must
    refuse, not silently drop tier>=1 rows from the per-tier stats."""
    t, n = 32, 2
    _, logits, _, _ = _mk_case(jax.random.PRNGKey(2), t, n, 32, 8)
    with pytest.raises(AssertionError, match="tier_margins"):
        D.make_dispatch_plan(logits, exact_cap=16, invoke_cap=8,
                             tier=_mixed_tier(t))
    # either escape hatch works
    p1 = D.make_dispatch_plan(logits, exact_cap=16, invoke_cap=8,
                              tier=_mixed_tier(t), n_tiers=3)
    p2 = D.make_dispatch_plan(logits, exact_cap=16, invoke_cap=8,
                              tier=_mixed_tier(t),
                              tier_margins=jnp.zeros((3,)))
    assert p1.n_tiers == p2.n_tiers == 3
    np.testing.assert_array_equal(np.asarray(p1.tier_counts),
                                  np.asarray(p2.tier_counts))


def test_tier_margins_are_traced_not_static():
    """One jitted program must serve every margin setting (and tier mix):
    the margins vector is an input, never a recompile trigger."""
    t, n, d, d_h = 64, 2, 32, 8
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(5), t, n, d, d_h)
    fn = jax.jit(lambda tr, tm: D.mcma_dispatch(
        x, logits, exact_fn, *w, exact_cap=t // 2, invoke_cap=t // 3,
        backend="xla", tier=tr, tier_margins=tm))
    invs = []
    for m in ([8.0, 0.0, -8.0], [0.0, 0.0, 0.0], [-8.0, 0.0, 8.0]):
        _, s = fn(_mixed_tier(t), jnp.asarray(m))
        invs.append(float(s["invocation"]))
    assert_zero_retrace(fn, "a margins change")
    # flipping the margins must actually change the routing
    assert invs[0] != invs[2]


# ---------------------------------------------------------------------------
# asymmetric per-class capacities
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_per_class_caps_clamp_each_class(backend):
    t, n, d, d_h = 128, 3, 48, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(13), t, n, d, d_h)
    caps = (4, 40, 17)
    y, s = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=t // 2,
                           invoke_cap=caps, backend=backend, block_t=32,
                           interpret=backend == "pallas")
    s = jax.tree.map(np.asarray, s)
    np.testing.assert_array_equal(
        s["dispatched"][1:], np.minimum(s["class_counts"][1:], caps))
    # executed capacity reflects the asymmetric budgets on the oracle
    if backend == "xla":
        assert int(s["executed_rows"]) == t // 2 + sum(caps)


def test_per_class_caps_pallas_matches_xla():
    t, n, d, d_h = 96, 3, 48, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(17), t, n, d, d_h)
    outs = {}
    for backend in ("xla", "pallas"):
        y, _ = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=t // 2,
                               invoke_cap=(3, 29, 11), backend=backend,
                               block_t=32, interpret=backend == "pallas")
        outs[backend] = np.asarray(y)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


def test_uniform_tuple_caps_equal_scalar_cap():
    t, n, d, d_h = 80, 2, 32, 8
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(19), t, n, d, d_h)
    y1, s1 = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=40,
                             invoke_cap=24, backend="xla")
    y2, s2 = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=40,
                             invoke_cap=(24, 24), backend="xla")
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(s1["dispatched"]),
                                  np.asarray(s2["dispatched"]))


def test_plan_from_asymmetric_operating_point():
    t, n = 80, 2
    _, logits, _, _ = _mk_case(jax.random.PRNGKey(23), t, n, 32, 8)
    pt = AT.OperatingPoint(0.5, 0.3, invoke_fracs=(0.3, 0.1))
    plan = D.make_dispatch_plan(logits, operating_point=pt)
    from repro.sharding.rules import shard_capacity
    assert plan.class_caps == (shard_capacity(t, 0.3),
                               shard_capacity(t, 0.1))
    assert pt.cost(n) == pytest.approx(0.5 + 0.3 + 0.1)


# ---------------------------------------------------------------------------
# ladder_from_counts: asymmetric rungs from a skewed served mix
# ---------------------------------------------------------------------------

def test_ladder_from_counts_skewed_mix():
    """A heavy-tailed mix (class 1 hot, class 2 cold) must yield rungs
    whose per-class fractions track the per-class quantiles — hot class
    gets capacity, cold class stops paying for uniform padding."""
    rng = np.random.default_rng(0)
    ticks, t, n = 64, 256, 3
    hot = rng.normal(150, 12, ticks).clip(0)        # class 1: hot
    mid = rng.normal(40, 8, ticks).clip(0)          # class 2: mid
    cold = rng.normal(6, 2, ticks).clip(0)          # class 3: cold tail
    exact = (t - hot - mid - cold).clip(0)
    counts = np.stack([exact, hot, mid, cold], -1)
    ladder = AT.ladder_from_counts(counts, t)
    assert len(ladder) >= 2
    # every derived rung (bar the escape hatch) is asymmetric: hot >> cold
    for pt in ladder[:-1]:
        assert pt.invoke_fracs[0] > pt.invoke_fracs[1] \
            > pt.invoke_fracs[2], pt
    # cost-ordered with the full-capacity escape rung last
    costs = [pt.cost(n) for pt in ladder]
    assert costs == sorted(costs)
    assert ladder[-1] == AT.OperatingPoint(1.0, 1.0,
                                           invoke_fracs=(1.0,) * n)
    # the mid rung covers median demand without uniform over-provisioning:
    # strictly cheaper than the uniform ladder sized for the hot class
    uniform_cost = (0.5 + n * (np.quantile(hot, 0.5) * 1.1 / t))
    assert ladder[0].cost(n) < uniform_cost
    # replaying the served counts against the top derived (non-escape)
    # rung stays under a 5% drop budget
    caps = AT.point_caps(ladder[-2], t, n)
    drops = np.maximum(counts - caps, 0).sum()
    assert drops / counts.sum() < 0.05


def test_ladder_from_counts_single_observation_and_controller():
    counts = np.asarray([100.0, 140.0, 10.0, 6.0])
    ladder = AT.ladder_from_counts(counts, 256)
    ctrl = AT.CapacityController(
        ladder, lambda pt: AT.point_caps(pt, 256, 3), drop_budget=0.05)
    idx = ctrl.observe({"class_counts": counts, "dropped": 0.0})
    assert 0 <= idx < len(ladder)


def test_margins_and_default_bounds():
    bounds = AT.default_tier_bounds(0.10)
    assert bounds == (0.05, 0.10, 0.20)
    m = AT.margins_from_bounds(bounds, 0.10, scale=4.0)
    assert m[1] == pytest.approx(0.0)                   # zero at the base
    assert m[0] > 0 > m[2]                              # tight > 0 > loose
    assert m[0] == pytest.approx(-m[2])                 # symmetric spread
    assert list(m) == sorted(m, reverse=True)           # monotone in bound


# ---------------------------------------------------------------------------
# server: submit-time validation + per-tier drain summary
# ---------------------------------------------------------------------------

def _server(**kw):
    from repro.runtime.server import DecodeServer
    cfg = _approx_cfg()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return DecodeServer(cfg, params, batch=4, max_len=64,
                        use_mcma_dispatch=True, **kw)


def test_submit_validates_error_bound():
    from repro.runtime.server import Request
    srv = _server(qos_tiers=(0.05, 0.10, 0.20))
    mk = lambda **kw: Request(rid=0, prompt=np.ones(3, np.int32), **kw)
    with pytest.raises(ValueError, match="tighter than the tightest"):
        srv.submit(mk(error_bound=0.01))
    with pytest.raises(ValueError, match="positive finite"):
        srv.submit(mk(error_bound=-0.1))
    with pytest.raises(ValueError, match="out of range"):
        srv.submit(mk(tier=7))
    # quantization: served at-or-tighter than asked, clamped to loosest
    for eb, want in ((0.05, 0), (0.07, 0), (0.10, 1), (0.15, 1),
                     (0.20, 2), (0.9, 2)):
        r = mk(error_bound=eb)
        srv.submit(r)
        assert r.tier == want, (eb, r.tier)


def test_submit_without_tier_table_fails_loudly():
    from repro.runtime.server import Request
    srv = _server()
    with pytest.raises(ValueError, match="no tier table"):
        srv.submit(Request(rid=0, prompt=np.ones(3, np.int32),
                           error_bound=0.1))


def test_qos_app_anchors_tier_table():
    """``qos_app`` pulls the quality.py bound from the apps registry: the
    tier table brackets it and the validation message names the app."""
    from repro.apps.registry import get_app
    from repro.runtime.server import Request
    srv = _server(qos_app="bessel")
    base = get_app("bessel").error_bound
    assert srv.tier_bounds == AT.default_tier_bounds(base)
    assert srv.tier_margins[1] == pytest.approx(0.0)
    with pytest.raises(ValueError, match="bessel"):
        srv.submit(Request(rid=0, prompt=np.ones(3, np.int32),
                           error_bound=base / 100))


@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_server_mixed_tier_drain_summary(route_scope):
    from repro.runtime.server import Request
    srv = _server(qos_tiers=(0.05, 0.10, 0.20), route_scope=route_scope)
    rng = np.random.default_rng(0)
    bounds = [0.05, 0.10, 0.25, None]
    reqs = [Request(rid=i, prompt=rng.integers(0, srv.cfg.vocab, 5)
                    .astype(np.int32), max_new=4,
                    error_bound=bounds[i % len(bounds)])
            for i in range(6)]
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained(max_ticks=300)
    assert all(r.done for r in reqs)
    per = stats["per_tier"]
    assert [p["tier"] for p in per] == [0, 1, 2]
    assert [p["error_bound"] for p in per] == [0.05, 0.10, 0.20]
    # every active row is attributed to exactly one tier
    assert sum(p["rows"] for p in per) == pytest.approx(srv.active_sum)
    for p in per:
        assert 0.0 <= p["served_invocation_rate"] <= 1.0
        assert 0.0 <= p["dropped_frac"] <= 1.0
        assert p["rows"] > 0          # the wave hit every tier
    # tight margins bias to exact: tier 0 must not out-invoke tier 2
    assert per[0]["served_invocation_rate"] \
        <= per[2]["served_invocation_rate"] + 1e-9
    # the served-count history feeds the ladder autotuner
    ladder = srv.derived_ladder()
    assert ladder[-1].exact_frac == 1.0
    assert all(len(pt.invoke_fracs) == srv.cfg.approx.n_approx
               for pt in ladder)


# ---------------------------------------------------------------------------
# mesh: mixed tiers on 8 virtual devices, plan built and consumed sharded
# ---------------------------------------------------------------------------

_TIER_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.sharding import activations as A

    def cfg_with(backend, scope):
        cfg = smoke_config(get_config("internlm2-1.8b"))
        return dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, enable=True, backend=backend, interpret=True,
            block_t=16, route_scope=scope, n_tiers=3))

    B = 8
    tier = jnp.asarray([0, 1, 2, 2, 1, 0, 1, 2], jnp.int32)
    margins = jnp.asarray([3.0, 0.0, -3.0])
    mask = jnp.asarray([True] * 6 + [False] * 2)
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    params = M.init_model(jax.random.PRNGKey(0), cfg_with("xla", "tick"))
    out = {}
    for scope in ("layer", "tick"):
        cfg = cfg_with("xla", scope)
        cache = M.init_cache(cfg, B, 32)
        # single-device reference: the psum'd per-tier mesh stats must
        # equal this split exactly (routing is row-wise)
        _, _, m1 = M.decode(cfg, params, cache, toks, serve=True,
                            collect_metrics=True, row_mask=mask,
                            tier=tier, tier_margins=margins)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        outs, tcs = {}, {}
        for backend in ("xla", "pallas"):
            c = cfg_with(backend, scope)
            with mesh, A.activation_sharding(P(("data",), None, None)):
                lg, _, m = jax.jit(lambda p, ca, t, rm, tr, tm, c_=c:
                    M.decode(c_, p, ca, t, serve=True, collect_metrics=True,
                             row_mask=rm, tier=tr, tier_margins=tm))(
                    params, cache, toks, mask, tier, margins)
            outs[backend] = np.asarray(lg)
            tcs[backend] = np.asarray(m["tier_counts"]).tolist()
        out[scope] = {
            "pallas_bitexact_vs_xla": bool(np.array_equal(outs["pallas"],
                                                          outs["xla"])),
            "tier_counts": tcs,
            "single_tier_counts": np.asarray(m1["tier_counts"]).tolist(),
            "rows": float(np.asarray(m1["tier_counts"]).sum()),
        }
    print("RESULT" + json.dumps(out))
""")


def test_mixed_tier_mesh_subprocess_8_virtual_devices():
    out = _run(_TIER_MESH)
    for scope in ("layer", "tick"):
        o = out[scope]
        assert o["pallas_bitexact_vs_xla"], scope
        # both backends agree on the psum'd per-tier split on the mesh
        assert o["tier_counts"]["pallas"] == o["tier_counts"]["xla"], scope
        assert o["rows"] == 6.0          # active rows only
    # tick scope routes ONCE from the (drift-free) embedding, so its
    # psum'd per-tier mesh stats equal the single-device split EXACTLY;
    # layer scope's deeper layers see TP-psum rounding in the hidden
    # state, so only the within-mesh backend equality above is pinned
    o = out["tick"]
    for be in ("xla", "pallas"):
        assert o["tier_counts"][be] == o["single_tier_counts"], be


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI multidevice leg: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@needs_8_devices
@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_mixed_tier_mesh_inprocess(route_scope):
    """CI multidevice leg: a mixed-tier batch on the (4, 2) mesh — pallas
    == xla bit-for-bit; at tick scope (routed once, from the drift-free
    embedding) the per-tier psum stats == the single-device split."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import activations as A
    b = 8
    tier = jnp.asarray([0, 1, 2, 2, 1, 0, 1, 2], jnp.int32)
    mask = jnp.asarray([True] * 6 + [False] * 2)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    params = M.init_model(jax.random.PRNGKey(0), _approx_cfg())
    cfg1 = _approx_cfg(route_scope=route_scope, n_tiers=3)
    cache = M.init_cache(cfg1, b, 32)
    _, _, m1 = M.decode(cfg1, params, cache, toks, serve=True,
                        collect_metrics=True, row_mask=mask, tier=tier,
                        tier_margins=MARGINS)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    outs, tcs = {}, {}
    for be in ("xla", "pallas"):
        c = _approx_cfg(backend=be, interpret=True, block_t=16,
                        route_scope=route_scope, n_tiers=3)
        with mesh, A.activation_sharding(P(("data",), None, None)):
            lg, _, m = jax.jit(lambda p, ca, t, rm, tr, tm, c_=c: M.decode(
                c_, p, ca, t, serve=True, collect_metrics=True,
                row_mask=rm, tier=tr, tier_margins=tm))(
                params, cache, toks, mask, tier, MARGINS)
        outs[be] = np.asarray(lg)
        tcs[be] = np.asarray(m["tier_counts"])
        assert float(tcs[be].sum()) == 6.0      # active rows only
        if route_scope == "tick":
            # per-tier ROUTED counts are sharding-invariant (row-wise
            # routing); dispatched counts are not — per-shard capacities
            # may drop rows a whole-batch budget would keep
            np.testing.assert_array_equal(tcs[be],
                                          np.asarray(m1["tier_counts"]))
        assert (np.asarray(m["tier_dispatched"]) <= tcs[be]).all()
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(tcs["pallas"], tcs["xla"])


def test_sharded_engine_mixed_tier_psum_equals_single_device():
    """mcma_dispatch_sharded with tiers: global per-tier stats == the
    single-device run over the same rows, exactly (subprocess, 8 virtual
    devices)."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime import dispatch as D

        T, N, DM, DH = 128, 3, 32, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 6)
        x = jax.random.normal(ks[0], (T, DM))
        router = jax.random.normal(ks[1], (DM, N + 1)) * 0.5
        w = [jax.random.normal(k, s) * 0.2 for k, s in zip(
            ks[2:], [(N, DM, DH), (N, DH), (N, DH, DM), (N, DM)])]
        wi = jax.random.normal(jax.random.fold_in(ks[0], 1), (DM, DM))
        wo = jax.random.normal(jax.random.fold_in(ks[0], 2), (DM, DM))
        exact_fn = lambda ep, xb: jnp.dot(jnp.dot(xb, ep[0]), ep[1])
        lg = x @ router
        tier = jnp.asarray(np.random.default_rng(0).integers(0, 3, T),
                           jnp.int32)
        margins = jnp.asarray([2.0, 0.0, -2.0])
        _, s1 = D.mcma_dispatch(x, lg, lambda xb: exact_fn((wi, wo), xb),
                                *w, exact_cap=T // 2, invoke_cap=T // 8,
                                backend="xla", tier=tier,
                                tier_margins=margins)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        _, s8 = D.mcma_dispatch_sharded(
            mesh, x, lg, exact_fn, (wi, wo), *w, exact_cap=T // 16,
            invoke_cap=T // 64, backend="xla", tier=tier,
            tier_margins=margins)
        print("RESULT" + json.dumps({
            "single_tc": np.asarray(s1["tier_counts"]).tolist(),
            "mesh_tc": np.asarray(s8["tier_counts"]).tolist(),
            "mesh_rows": float(np.asarray(s8["tier_counts"]).sum()),
        }))
    """))
    # routing is row-wise: the psum'd per-tier ROUTED counts are identical
    # to the single-device split no matter how the batch is sharded
    assert out["mesh_tc"] == out["single_tc"]
    assert out["mesh_rows"] == 128.0
