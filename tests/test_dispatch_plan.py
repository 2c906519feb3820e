"""Plan/execute dispatch architecture (runtime/dispatch.py) and tick-level
routing (ApproxConfig.route_scope="tick").

Pins, per the PR's acceptance criteria:
  * make_dispatch_plan + execute_dispatch == the one-shot mcma_dispatch
    bit-for-bit on CPU f32 — both backends, with and without row_mask;
  * one plan reused across L layers' weights == L independent per-layer
    dispatches when the per-layer logits are identical (plan reuse is a
    pure refactor of the compute, not a semantics change);
  * tick-scope decode: pallas == xla oracle on 1 device and on the
    8-virtual-device (data, model) mesh (subprocess + in-process CI-leg
    variants), with the plan built and consumed inside the same sharding;
  * the grad-accum metrics fix and the hybrid decode metrics fix (the two
    satellite bugs), and the tick-router head's co-training signal.
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
from repro.models import model as M
from repro.runtime import dispatch as D
from repro.runtime import steps as S

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


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


# ---------------------------------------------------------------------------
# plan + execute == the one-shot engine, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("with_mask", [False, True])
def test_plan_execute_matches_mcma_dispatch(backend, with_mask):
    t, n, d, d_h, block = 96, 3, 48, 16, 32
    key = jax.random.PRNGKey(11)
    x, logits, w, exact_fn = _mk_case(key, t, n, d, d_h)
    rm = (jnp.arange(t) % 5 != 0) if with_mask else None
    caps = dict(exact_cap=t // 2, invoke_cap=max(int(t * 0.3), 1))
    kw = dict(backend=backend, block_t=block)
    interp = backend == "pallas"

    plan = D.make_dispatch_plan(logits, rm, **caps, **kw)
    y = D.execute_dispatch(plan, x, exact_fn, *w, interpret=interp)
    y_ref, s_ref = D.mcma_dispatch(x, logits, exact_fn, *w, row_mask=rm,
                                   interpret=interp, **caps, **kw)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    s = D.plan_invoke_stats(plan)
    assert set(s) == set(s_ref)
    for k in s:
        np.testing.assert_array_equal(np.asarray(s[k]), np.asarray(s_ref[k]))


def test_plan_from_operating_point_matches_explicit_caps():
    from repro.runtime.autotune import OperatingPoint
    from repro.sharding.rules import shard_capacity
    t, n = 80, 2
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(3), t, n, 32, 8)
    pt = OperatingPoint(0.5, 0.3)
    p1 = D.make_dispatch_plan(logits, operating_point=pt)
    p2 = D.make_dispatch_plan(
        logits, exact_cap=shard_capacity(t, 0.5),
        invoke_cap=shard_capacity(t, 0.3))
    assert (p1.exact_cap, p1.invoke_cap) == (p2.exact_cap, p2.invoke_cap)
    np.testing.assert_array_equal(np.asarray(p1.cls), np.asarray(p2.cls))


def test_plan_is_a_pytree_and_jit_stable():
    """A DispatchPlan must flow through jit boundaries (the decode step
    builds it inside the jitted tick) with its static meta intact."""
    t, n = 64, 2
    _, logits, _, _ = _mk_case(jax.random.PRNGKey(5), t, n, 32, 8)
    f = jax.jit(lambda lg: D.make_dispatch_plan(lg, exact_cap=32,
                                                invoke_cap=16))
    plan = f(logits)
    assert plan.n_approx == n and plan.exact_cap == 32
    leaves = jax.tree_util.tree_leaves(plan)
    assert len(leaves) == len(D._PLAN_DATA)
    again = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(plan), leaves)
    assert again.backend == plan.backend


# ---------------------------------------------------------------------------
# plan reuse across layers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_plan_reused_across_layers_matches_per_layer_dispatch(backend):
    """When every layer's logits agree, ONE plan executed against L
    different weight sets must equal L independent per-layer dispatches —
    the semantic guarantee that makes tick-scope hoisting sound."""
    t, n, d, d_h, block, L = 64, 2, 32, 8, 16, 4
    key = jax.random.PRNGKey(29)
    x, logits, _, _ = _mk_case(key, t, n, d, d_h)
    layers = [_mk_case(jax.random.fold_in(key, i + 1), t, n, d, d_h)[2:]
              for i in range(L)]
    caps = dict(exact_cap=t // 2, invoke_cap=max(int(t * 0.4), 1))
    interp = backend == "pallas"

    plan = D.make_dispatch_plan(logits, backend=backend, block_t=block,
                                **caps)
    for w, exact_fn in layers:
        y_plan = D.execute_dispatch(plan, x, exact_fn, *w, interpret=interp)
        y_ref, _ = D.mcma_dispatch(x, logits, exact_fn, *w, backend=backend,
                                   block_t=block, interpret=interp, **caps)
        np.testing.assert_array_equal(np.asarray(y_plan), np.asarray(y_ref))


# ---------------------------------------------------------------------------
# tick-scope decode: one plan above the layer scan
# ---------------------------------------------------------------------------

def test_tick_decode_pallas_matches_xla_oracle():
    cfg = _approx_cfg()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    assert "tick_router" in params
    b = 4
    cache = M.init_cache(cfg, b, 32)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    mask = jnp.asarray([True, True, False, True])
    outs, stats = {}, {}
    for be, kw in (("xla", {}),
                   ("pallas", dict(interpret=True, block_t=16))):
        c = _approx_cfg(backend=be, route_scope="tick", **kw)
        lg, _, m = M.decode(c, params, cache, toks, serve=True,
                            collect_metrics=True, row_mask=mask)
        outs[be], stats[be] = np.asarray(lg), jax.tree.map(np.asarray, m)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(stats["pallas"]["class_counts"],
                                  stats["xla"]["class_counts"])
    # the plan embeds the row mask: only the 3 active rows are routed
    assert int(stats["xla"]["class_counts"].sum()) == 3


def test_tick_decode_metrics_are_the_plan_stats():
    """Every layer executes the SAME plan, so the layer-meaned metrics the
    step reports must equal the plan's tick-level stats exactly — one
    observation per tick for the autotuner, not L noisy ones."""
    cfg = _approx_cfg(route_scope="tick")
    params = M.init_model(jax.random.PRNGKey(1), cfg)
    b = 4
    cache = M.init_cache(cfg, b, 32)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    from repro.models.approx_ffn import make_tick_plan
    x = M.L.embed_fwd(cfg, params["embed"], toks)
    plan = make_tick_plan(cfg, params, x)
    want = jax.tree.map(np.asarray, D.plan_invoke_stats(plan))
    _, _, m = M.decode(cfg, params, cache, toks, serve=True,
                       collect_metrics=True)
    np.testing.assert_array_equal(np.asarray(m["class_counts"]),
                                  want["class_counts"])
    np.testing.assert_array_equal(np.asarray(m["dispatched"]),
                                  want["dispatched"])
    assert float(m["invocation"]) == pytest.approx(
        float(want["invocation"]), abs=1e-7)


def test_decode_server_tick_scope_end_to_end():
    from repro.runtime.server import DecodeServer, Request
    cfg = _approx_cfg()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    server = DecodeServer(cfg, params, batch=2, max_len=64,
                          use_mcma_dispatch=True, route_scope="tick")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 5)
                    .astype(np.int32), max_new=4) for i in range(3)]
    for r in reqs:
        server.submit(r)
    stats = server.run_until_drained(max_ticks=300)
    assert all(r.done for r in reqs)
    assert 0.0 <= stats["invocation_rate"] <= 1.0
    assert "routed_per_class" in stats


# ---------------------------------------------------------------------------
# tick scope on the mesh: plan built and consumed in the same sharding
# ---------------------------------------------------------------------------

_TICK_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.sharding import activations as A

    def cfg_with(backend):
        cfg = smoke_config(get_config("internlm2-1.8b"))
        return dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, enable=True, backend=backend, interpret=True,
            block_t=16, route_scope="tick"))

    cfg = cfg_with("xla")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    B = 8
    cache = M.init_cache(cfg, B, 32)
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    mask = jnp.asarray([True] * 6 + [False] * 2)

    # single-device reference (routing is row-wise, so the psum-reduced
    # mesh counts must equal these exactly)
    _, _, m1 = M.decode(cfg, params, cache, toks, serve=True,
                        collect_metrics=True, row_mask=mask)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    outs, counts = {}, {}
    for backend in ("xla", "pallas"):
        c = cfg_with(backend)
        with mesh, A.activation_sharding(P(("data",), None, None)):
            lg, _, m = jax.jit(lambda p, ca, t, rm, c_=c: M.decode(
                c_, p, ca, t, serve=True, collect_metrics=True,
                row_mask=rm))(params, cache, toks, mask)
        outs[backend] = np.asarray(lg)
        counts[backend] = np.asarray(m["class_counts"]).tolist()
    out = {
        "pallas_bitexact_vs_xla": bool(np.array_equal(outs["pallas"],
                                                      outs["xla"])),
        "counts": counts,
        "single_counts": np.asarray(m1["class_counts"]).tolist(),
        "counts_sum": float(np.asarray(m1["class_counts"]).sum()),
    }
    print("RESULT" + json.dumps(out))
""")


def test_tick_scope_mesh_subprocess_8_virtual_devices():
    out = _run(_TICK_MESH)
    assert out["pallas_bitexact_vs_xla"]
    for be in ("xla", "pallas"):
        assert out["counts"][be] == out["single_counts"], out
    assert out["counts_sum"] == 6.0  # active rows only


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI multidevice leg: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@needs_8_devices
def test_tick_scope_mesh_inprocess():
    """CI multidevice leg: tick-scope decode on a (4, 2) mesh — pallas ==
    xla bit-for-bit, plan counts == the single-device routing."""
    from repro.sharding import activations as A
    from jax.sharding import PartitionSpec as P
    params = M.init_model(jax.random.PRNGKey(0), _approx_cfg())
    b = 8
    cache = M.init_cache(_approx_cfg(), b, 32)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    mask = jnp.asarray([True] * 6 + [False] * 2)
    cfg1 = _approx_cfg(route_scope="tick")
    _, _, m1 = M.decode(cfg1, params, cache, toks, serve=True,
                        collect_metrics=True, row_mask=mask)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    outs = {}
    for be in ("xla", "pallas"):
        c = _approx_cfg(backend=be, interpret=True, block_t=16,
                        route_scope="tick")
        with mesh, A.activation_sharding(P(("data",), None, None)):
            lg, _, m = jax.jit(lambda p, ca, t, rm, c_=c: M.decode(
                c_, p, ca, t, serve=True, collect_metrics=True,
                row_mask=rm))(params, cache, toks, mask)
        outs[be] = np.asarray(lg)
        np.testing.assert_array_equal(np.asarray(m["class_counts"]),
                                      np.asarray(m1["class_counts"]))
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


# ---------------------------------------------------------------------------
# satellite fixes: grad-accum metrics, hybrid decode metrics, tick co-train
# ---------------------------------------------------------------------------

def test_grad_accum_preserves_metrics():
    """grad_accum > 1 used to return metrics = {} — the invocation /
    router_acc / block metrics must survive the accumulation scan and,
    with equal-sized microbatches, equal the single-shot values."""
    cfg = _approx_cfg()
    state = S.init_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"inputs": jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)),
                                   jnp.int32)}
    _, m1 = S.make_train_step(cfg, grad_accum=1)(state, batch)
    _, m2 = S.make_train_step(cfg, grad_accum=2)(state, batch)
    for k in ("invocation", "router_acc", "lm_loss", "tick_router_acc"):
        assert k in m2, (k, sorted(m2))
        assert float(m2[k]) == pytest.approx(float(m1[k]), abs=1e-5), k
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), abs=1e-5)


def test_grad_accum_matches_single_shot_gradients():
    """The fix must not perturb the accumulated gradients themselves."""
    cfg = _approx_cfg()
    state = S.init_train_state(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    batch = {"inputs": jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)),
                                   jnp.int32)}
    s1, _ = S.make_train_step(cfg, grad_accum=1)(state, batch)
    s2, _ = S.make_train_step(cfg, grad_accum=2)(state, batch)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         s1["params"], s2["params"])
    assert max(jax.tree_util.tree_leaves(diffs)) < 1e-5


@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_hybrid_decode_collects_dispatch_metrics(route_scope):
    """model.decode's hybrid group body used to drop the shared block's
    metrics (x, nc, _, _ = ...), so collect_metrics returned {} and the
    autotuner was blind for the zamba2 family."""
    cfg = smoke_config(get_config("zamba2-2.7b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, route_scope=route_scope))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    assert "tick_router" in params
    b = 2
    cache = M.init_cache(cfg, b, 32)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    lg, _, m = M.decode(cfg, params, cache, toks, serve=True,
                        collect_metrics=True)
    assert "invocation" in m and "class_counts" in m, sorted(m)
    assert int(np.asarray(m["class_counts"]).sum()) == b
    assert np.isfinite(np.asarray(lg)).all()


def test_hybrid_tick_decode_pallas_matches_xla():
    base = smoke_config(get_config("zamba2-2.7b"))
    outs = {}
    for be, kw in (("xla", {}),
                   ("pallas", dict(interpret=True, block_t=16))):
        cfg = dataclasses.replace(base, approx=dataclasses.replace(
            base.approx, enable=True, backend=be, route_scope="tick", **kw))
        params = M.init_model(jax.random.PRNGKey(0), cfg)
        cache = M.init_cache(cfg, 2, 32)
        toks = jnp.asarray([[3], [5]], jnp.int32)
        lg, _, _ = M.decode(cfg, params, cache, toks, serve=True,
                            collect_metrics=True)
        outs[be] = np.asarray(lg)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


def test_unknown_route_scope_raises():
    """A typo'd scope must fail loudly, not silently run layer routing."""
    with pytest.raises(ValueError, match="route_scope"):
        S.make_decode_step(_approx_cfg(), route_scope="ticks")
    cfg = _approx_cfg(route_scope="Tick")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    cache = M.init_cache(cfg, 2, 32)
    with pytest.raises(ValueError, match="route_scope"):
        M.decode(cfg, params, cache, jnp.ones((2, 1), jnp.int32), serve=True)


def test_tick_router_head_cotrains():
    """The tick router must receive gradient signal from the aggregated
    competitive labels (its loss rides the aux channel)."""
    cfg = _approx_cfg()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    inputs = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    loss, metrics = M.lm_loss(cfg, params, inputs, labels)
    assert "tick_router_loss" in metrics and "tick_router_acc" in metrics
    assert 0.0 <= float(metrics["tick_router_acc"]) <= 1.0
    g = jax.grad(lambda p: M.lm_loss(cfg, p, inputs, labels)[0])(params)
    assert float(jnp.linalg.norm(g["tick_router"])) > 0.0
