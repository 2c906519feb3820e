"""Capacity autotuning (runtime/autotune.py) and the free-slot router-bias
fix it depends on.

The controller is pure host-side python over the engine's invoke_stats, so
its law is unit-tested directly; the acceptance invariant (skewed mix ->
operating point under the drop budget with strictly more served
invocation than static, pallas == xla at every point) runs against the
real engine single-device AND on an 8-virtual-device mesh (subprocess,
the test_sharding.py pattern).  The mask fix is pinned by equating a
half-empty slot table with its dense sub-batch, at the engine and at the
DecodeServer level.
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

from repro.configs.registry import get_config, smoke_config
from repro.runtime import dispatch as D
from repro.runtime.autotune import (CapacityController, OperatingPoint,
                                    default_ladder, point_caps)

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


def _run(script: str, timeout: int = 600) -> dict:
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.split("RESULT")[1])


# ---------------------------------------------------------------------------
# Controller law (host-side, no engine)
# ---------------------------------------------------------------------------

def _ctrl(ladder=None, t=100, n=3, **kw):
    ladder = ladder or (OperatingPoint(0.5, 0.1), OperatingPoint(0.5, 0.3),
                        OperatingPoint(1.0, 1.0))
    kw.setdefault("cooldown", 0)
    kw.setdefault("down_patience", 2)
    return CapacityController(
        ladder, lambda pt: point_caps(pt, t, n), drop_budget=0.05, **kw)


def _stats(counts):
    counts = np.asarray(counts, float)
    return {"class_counts": counts, "dropped": 0.0}


def test_controller_steps_up_to_first_sufficient_rung():
    c = _ctrl()
    # 60 rows hot on class 1 vs invoke_cap 10 at rung 0: 50 dropped
    s = {"class_counts": np.asarray([40., 60., 0., 0.]), "dropped": 50.0}
    idx = c.observe(s)
    # rung 1 (cap 30) still drops 30; rung 2 (cap 100) is the first fit
    assert idx == 2
    assert c.history[0].from_index == 0 and c.history[0].to_index == 2


def test_controller_steps_down_with_patience_and_hysteresis():
    c = _ctrl(start=2)
    # light mix: fits every rung's caps (exact 45 < 50, per-class <= 10)
    light = {"class_counts": np.asarray([45., 4., 3., 3.]), "dropped": 0.0}
    assert c.observe(light) == 2          # patience 1/2
    assert c.observe(light) == 1          # patience reached -> one rung
    # one rung at a time, and the new rung needs fresh patience
    assert c.observe(light) == 1
    assert c.observe(light) == 0


def test_controller_cooldown_blocks_consecutive_switches():
    c = _ctrl(cooldown=3)
    hot = {"class_counts": np.asarray([0., 100., 0., 0.]), "dropped": 90.0}
    assert c.observe(hot) == 2
    light = {"class_counts": np.asarray([45., 0., 0., 0.]), "dropped": 0.0}
    for _ in range(3):                    # inside cooldown: frozen
        assert c.observe(light) == 2
    for _ in range(2):
        c.observe(light)
    assert c.index == 1                   # then the down path resumes


def test_controller_backoff_dampens_thrash():
    """A mix the prediction clears but reality drops (the layer-mean /
    cross-shard-skew case) must not oscillate forever: each re-escalation
    doubles the patience before the next down attempt."""
    c = _ctrl(down_patience=1)
    # at rung 1: prediction from these counts fits rung 0... but observed
    # drops say otherwise once we get there
    deceptive_ok = {"class_counts": np.asarray([45., 5., 0., 0.]),
                    "dropped": 0.0}
    deceptive_bad = {"class_counts": np.asarray([45., 5., 0., 0.]),
                     "dropped": 40.0}
    c.index = 1
    downs = []
    for i in range(64):
        # reality: rung 0 drops hard, higher rungs don't
        idx = c.observe(deceptive_bad if c.index == 0 else deceptive_ok)
        if c.history and c.history[-1].to_index < c.history[-1].from_index \
                and (not downs or c.history[-1].tick != downs[-1]):
            downs.append(c.history[-1].tick)
    assert len(downs) >= 2
    gaps = np.diff(downs)
    assert (gaps[1:] >= gaps[:-1]).all(), gaps   # monotone non-decreasing
    assert c._down_hold > 1                      # backoff engaged


def test_default_ladder_ordered_and_bracketing():
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))
    lad = default_ladder(cfg)
    a = cfg.approx
    costs = [p.cost(a.n_approx) for p in lad]
    assert costs == sorted(costs)
    assert OperatingPoint(a.exact_frac, a.invoke_frac, a.shard_slack) in lad
    assert lad[-1] == OperatingPoint(1.0, 1.0, a.shard_slack)
    assert len(set(lad)) == len(lad)


# ---------------------------------------------------------------------------
# Free-slot bias fix: masked dispatch == dense sub-batch
# ---------------------------------------------------------------------------

def _mk_case(key, t, n, d, d_h):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (d, n + 1)) * 0.5
    w = (jax.random.normal(ks[2], (n, d, d_h)) * 0.2,
         jax.random.normal(ks[3], (n, d_h)) * 0.1,
         jax.random.normal(ks[4], (n, d_h, d)) * 0.2,
         jax.random.normal(ks[5], (n, d)) * 0.1)
    wi = jax.random.normal(jax.random.fold_in(key, 7), (d, 2 * d)) * 0.1
    wo = jax.random.normal(jax.random.fold_in(key, 8), (2 * d, d)) * 0.1
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    return x, x @ router, w, exact_fn


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_half_empty_mask_equals_dense_batch(backend):
    """Regression for the free-slot router bias: a half-active row mask
    must yield the SAME invoke_stats as dispatching only the active rows,
    and identical outputs on them (idle rows exactly zero)."""
    t, n, d, d_h = 128, 3, 48, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(3), t, n, d, d_h)
    kw = dict(exact_cap=t // 2, invoke_cap=t // 3, backend=backend)
    if backend == "pallas":
        kw.update(block_t=32, interpret=True)
    mask = jnp.arange(t) < t // 2
    ym, sm = D.mcma_dispatch(x, logits, exact_fn, *w, row_mask=mask, **kw)
    yd, sd = D.mcma_dispatch(x[:t // 2], logits[:t // 2], exact_fn, *w, **kw)
    np.testing.assert_array_equal(np.asarray(sm["class_counts"]),
                                  np.asarray(sd["class_counts"]))
    np.testing.assert_array_equal(np.asarray(sm["dispatched"]),
                                  np.asarray(sd["dispatched"]))
    assert int(sm["dropped"]) == int(sd["dropped"])
    assert float(sm["invocation"]) == pytest.approx(
        float(sd["invocation"]), abs=1e-7)
    assert int(sm["class_counts"].sum()) == t // 2     # idle rows excluded
    np.testing.assert_allclose(np.asarray(ym)[:t // 2], np.asarray(yd),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(ym)[t // 2:].any()           # idle rows -> zero


def test_all_false_mask_reports_zero_invocation():
    """A fully idle batch must report invocation 0.0 (not the 1.0 that
    1 - 0/max(0,1) would claim) and all-zero counts/outputs."""
    t, n, d, d_h = 64, 2, 32, 8
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(6), t, n, d, d_h)
    y, s = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=16,
                           invoke_cap=16, backend="xla",
                           row_mask=jnp.zeros((t,), bool))
    assert float(s["invocation"]) == 0.0
    assert float(s["exact_frac"]) == 0.0
    assert int(s["class_counts"].sum()) == 0
    assert not np.asarray(y).any()


def test_all_true_mask_is_identity():
    """mask of all-True must trace to the exact same numbers as no mask."""
    t, n, d, d_h = 96, 2, 32, 8
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(4), t, n, d, d_h)
    kw = dict(exact_cap=t // 2, invoke_cap=t // 3, backend="xla")
    y0, s0 = D.mcma_dispatch(x, logits, exact_fn, *w, **kw)
    y1, s1 = D.mcma_dispatch(x, logits, exact_fn, *w,
                             row_mask=jnp.ones((t,), bool), **kw)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(s0["class_counts"]),
                                  np.asarray(s1["class_counts"]))


def test_server_half_empty_table_matches_batch1_invocation():
    """DecodeServer end-to-end: one request on a 4-slot table must report
    the SAME invocation rate (and tokens) as on a 1-slot table — the
    free slots no longer enter the router stats."""
    from repro.models import model as M
    from repro.runtime.server import DecodeServer, Request
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(1, 9, dtype=np.int32)
    outs = []
    for batch in (1, 4):
        srv = DecodeServer(cfg, params, batch=batch, max_len=64,
                           use_mcma_dispatch=True)
        r = Request(rid=0, prompt=prompt, max_new=5)
        srv.submit(r)
        stats = srv.run_until_drained(200)
        outs.append((r.out, stats["invocation_rate"],
                     stats["served_invocation_rate"]))
    assert outs[0][0] == outs[1][0]
    assert outs[0][1] == pytest.approx(outs[1][1], abs=1e-9)
    assert outs[0][2] == pytest.approx(outs[1][2], abs=1e-9)


# ---------------------------------------------------------------------------
# Acceptance: autotune on a skewed mix, single-device engine
# ---------------------------------------------------------------------------

def _hot_logits(key, t, n, hot, hot_frac):
    ks = jax.random.split(key, 2)
    cls = jnp.where(jax.random.uniform(ks[0], (t,)) < hot_frac, hot,
                    jax.random.randint(ks[1], (t,), 0, n + 1))
    return jax.nn.one_hot(cls, n + 1) * 10.0


def test_autotune_converges_under_budget_and_beats_static():
    """Skewed mix where the static config drops >10% of approximable
    rows: the controller must settle under the drop budget with strictly
    more served approximator rows than static, and the Pallas backend
    must match the XLA oracle bit-for-bit at EVERY visited point."""
    t, n, d, d_h = 256, 3, 48, 16
    budget = 0.05
    x, _, w, exact_fn = _mk_case(jax.random.PRNGKey(11), t, n, d, d_h)
    ladder = (OperatingPoint(0.5, 0.15), OperatingPoint(0.5, 0.35),
              OperatingPoint(1.0, 1.0))
    ctrl = CapacityController(ladder, lambda pt: point_caps(pt, t, n),
                              drop_budget=budget, cooldown=1,
                              down_patience=4)

    def run(idx, lg, backend):
        pt = ladder[idx]
        kw = dict(exact_cap=max(int(t * pt.exact_frac), 1),
                  invoke_cap=max(int(t * pt.invoke_frac), 1))
        if backend == "pallas":
            kw.update(block_t=32, interpret=True)
        return D.mcma_dispatch(x, lg, exact_fn, *w, backend=backend, **kw)

    static_drop = static_served = 0.0
    tuned_served = 0.0
    drops = []
    for tick in range(16):
        lg = _hot_logits(jax.random.fold_in(jax.random.PRNGKey(5), tick),
                         t, n, hot=n, hot_frac=0.8)
        yx, sx = run(ctrl.index, lg, "xla")
        yp, sp = run(ctrl.index, lg, "pallas")
        np.testing.assert_array_equal(np.asarray(yp), np.asarray(yx))
        _, ss = run(0, lg, "xla")
        static_drop += float(ss["dropped"])
        static_served += float(np.asarray(ss["dispatched"])[1:].sum())
        tuned_served += float(np.asarray(sx["dispatched"])[1:].sum())
        drops.append(float(sx["dropped"]) / t)
        ctrl.observe(jax.tree.map(np.asarray, sx))
    approximable = 0.8 * t * 16                     # ~hot rows alone
    assert static_drop / approximable > 0.10        # the premise holds
    assert np.mean(drops[-4:]) <= budget            # converged under budget
    assert tuned_served > static_served             # strictly more invoked
    assert ctrl.index > 0                           # actually moved


# ---------------------------------------------------------------------------
# Acceptance: the same invariant on an 8-virtual-device mesh (subprocess)
# ---------------------------------------------------------------------------

_MESH_AUTOTUNE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime import dispatch as D
    from repro.runtime.autotune import (CapacityController, OperatingPoint,
                                        point_caps)
    from repro.sharding.rules import shard_capacity

    T, N, DC, DH, DEVS, BLOCK = 128, 3, 32, 16, 8, 8
    TL = T // DEVS
    BUDGET = 0.05
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    x = jax.random.normal(ks[0], (T, DC), jnp.float32) * 0.5
    w1 = jax.random.normal(ks[2], (N, DC, DH)) * 0.2
    b1 = jax.random.normal(ks[3], (N, DH)) * 0.1
    w2 = jax.random.normal(ks[4], (N, DH, DC)) * 0.2
    b2 = jax.random.normal(ks[5], (N, DC)) * 0.1
    wi = jax.random.normal(ks[6], (DC, 2 * DC)) * 0.1
    wo = jax.random.normal(ks[7], (2 * DC, DC)) * 0.1
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((DEVS,), ("data",))

    ladder = (OperatingPoint(0.5, 0.15), OperatingPoint(0.5, 0.35),
              OperatingPoint(1.0, 1.0))
    ctrl = CapacityController(
        ladder, lambda pt: point_caps(pt, TL, N, n_shards=DEVS),
        drop_budget=BUDGET, cooldown=1, down_patience=4)

    def hot_logits(key, hot_frac):
        k1, k2 = jax.random.split(key)
        cls = jnp.where(jax.random.uniform(k1, (T,)) < hot_frac, N,
                        jax.random.randint(k2, (T,), 0, N + 1))
        return jax.nn.one_hot(cls, N + 1) * 10.0

    fns = {}            # (rung, backend) -> jitted engine (never retraced)

    def run(idx, lg, backend):
        if (idx, backend) not in fns:
            pt = ladder[idx]
            ec = shard_capacity(TL, pt.exact_frac, slack=pt.shard_slack)
            ic = shard_capacity(TL, pt.invoke_frac, slack=pt.shard_slack)
            fns[(idx, backend)] = jax.jit(
                lambda a, b, be=backend, e=ec, i=ic:
                D.mcma_dispatch_sharded(
                    mesh, a, b, exact_fn_p, (wi, wo), w1, b1, w2, b2,
                    exact_cap=e, invoke_cap=i, backend=be, block_t=BLOCK,
                    interpret=(be == "pallas")))
        return fns[(idx, backend)](x, lg)

    static_drop = static_served = tuned_served = 0.0
    drops, bitexact = [], True
    TICKS = 12
    for tick in range(TICKS):
        lg = hot_logits(jax.random.fold_in(jax.random.PRNGKey(5), tick),
                        0.8)
        yx, sx = run(ctrl.index, lg, "xla")
        yp, sp = run(ctrl.index, lg, "pallas")
        bitexact &= bool(np.array_equal(np.asarray(yp), np.asarray(yx)))
        _, ss = run(0, lg, "xla")
        static_drop += float(ss["dropped"])
        static_served += float(np.asarray(ss["dispatched"])[1:].sum())
        tuned_served += float(np.asarray(sx["dispatched"])[1:].sum())
        drops.append(float(sx["dropped"]) / T)
        ctrl.observe(jax.tree.map(np.asarray, sx))
    print("RESULT" + json.dumps({
        "static_drop_frac_of_hot": static_drop / (0.8 * T * TICKS),
        "tail_drop": float(np.mean(drops[-4:])),
        "tuned_served": tuned_served, "static_served": static_served,
        "final_index": ctrl.index, "bitexact": bitexact,
        "budget": BUDGET}))
""")


def test_autotune_mesh_converges_under_budget_subprocess():
    out = _run(_MESH_AUTOTUNE, timeout=900)
    assert out["bitexact"]
    assert out["static_drop_frac_of_hot"] > 0.10
    assert out["tail_drop"] <= out["budget"]
    assert out["tuned_served"] > out["static_served"]
    assert out["final_index"] > 0


# ---------------------------------------------------------------------------
# DecodeServer integration
# ---------------------------------------------------------------------------

def test_server_autotune_end_to_end_reports_trajectory():
    from repro.models import model as M
    from repro.runtime.server import DecodeServer, Request
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    ladder = (OperatingPoint(0.25, 0.1), OperatingPoint(1.0, 1.0))
    srv = DecodeServer(cfg, params, batch=2, max_len=64,
                       use_mcma_dispatch=True, autotune=ladder,
                       drop_budget=0.05,
                       autotune_kwargs=dict(cooldown=1, down_patience=4))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 5)
                    .astype(np.int32), max_new=4) for i in range(3)]
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained(300)
    assert all(r.done for r in reqs)
    at = stats["autotune"]
    assert 0 <= at["final_index"] < len(ladder)
    assert at["ticks"] == stats["ticks"]
    for s in at["switches"]:
        assert 0 <= s["from_index"] < len(ladder)
        assert 0 <= s["to_index"] < len(ladder)
    # the satellite-3 observability fields are present and consistent
    assert stats["dropped_rows"] >= 0.0
    disp = np.asarray(stats["dispatched_per_class"])
    routed = np.asarray(stats["routed_per_class"])
    assert disp.shape == routed.shape == (cfg.approx.n_approx + 1,)
    assert (disp <= routed + 1e-6).all()
    assert 0.0 <= stats["served_invocation_rate"] <= 1.0
