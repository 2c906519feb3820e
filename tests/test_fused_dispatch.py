"""Fused dispatch kernel (kernels/fused_dispatch.py) gates.

``backend="pallas_fused"`` folds the class-sort gather and the inverse-
permutation scatter into the weight-switch kernel via a second scalar-
prefetch operand (the plan's row-index vector).  The contract tested
here: BITWISE equality with the unfused pallas backend (same compute
shapes tile by tile), oracle-level (<1e-6) equality with the XLA
engine, at most ONE standalone activation gather and ONE scatter per
layer in the traced program (the exact-path capacity buffers — vs 3 of
each under unfused pallas), and zero retraces across every traced
input (mask, tiers, margins, residency).
"""
from __future__ import annotations

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
from repro.analysis.opcount import activation_moves
from repro.configs.registry import get_config, smoke_config
from repro.kernels import ops
from repro.models import model as M
from repro.runtime import dispatch as D

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


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


# ---------------------------------------------------------------------------
# engine: fused == unfused pallas (bitwise) == xla oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,n,d,d_h,block", [
    (200, 3, 64, 32, 64),     # generous capacity, mixed classes
    (37, 2, 24, 8, 32),       # T < block_t
    (128, 1, 32, 16, 64),     # single approximator
    (96, 5, 40, 8, 16),       # many classes, some likely sparse
])
@pytest.mark.parametrize("masked", [False, True])
def test_fused_matches_unfused_and_oracle(t, n, d, d_h, block, masked):
    key = jax.random.PRNGKey(t * 131 + n)
    x, logits, w, exact_fn = _mk_case(key, t, n, d, d_h)
    mask = (jnp.arange(t) % 5 != 0) if masked else None
    caps = dict(exact_cap=max(t // 2, 1), invoke_cap=max(int(t * 0.4), 1),
                row_mask=mask)
    yx, sx = D.mcma_dispatch(x, logits, exact_fn, *w, backend="xla", **caps)
    yp, sp = D.mcma_dispatch(x, logits, exact_fn, *w, backend="pallas",
                             block_t=block, interpret=True, **caps)
    yf, sf = D.mcma_dispatch(x, logits, exact_fn, *w, backend="pallas_fused",
                             block_t=block, interpret=True, **caps)
    # the fused kernel runs the SAME compute shapes tile by tile as the
    # unfused one — bitwise, not approximately
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yp))
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yx),
                               rtol=1e-6, atol=1e-6)
    for k in ("class_counts", "dispatched", "dropped"):
        np.testing.assert_array_equal(np.asarray(sf[k]), np.asarray(sx[k]))


def test_fused_mixed_qos_tiers_and_asymmetric_caps():
    t, n = 160, 3
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(5), t, n, 48, 16)
    tier = jnp.arange(t, dtype=jnp.int32) % 3
    margins = jnp.asarray([0.8, 0.0, -0.8], jnp.float32)
    caps = dict(exact_cap=t // 2, invoke_cap=(48, 32, 16),
                tier=tier, tier_margins=margins)
    outs = {}
    for be in D.DISPATCH_BACKENDS:
        interp = be in D.PALLAS_BACKENDS
        outs[be] = np.asarray(D.mcma_dispatch(
            x, logits, exact_fn, *w, backend=be, block_t=32,
            interpret=interp, **caps)[0])
    np.testing.assert_array_equal(outs["pallas_fused"], outs["pallas"])
    np.testing.assert_allclose(outs["pallas_fused"], outs["xla"],
                               rtol=1e-6, atol=1e-6)


def test_fused_residency_swap_bitexact_and_zero_retrace():
    t, lib, d, d_h = 96, 6, 32, 16
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    logits = x @ (jax.random.normal(ks[1], (d, lib + 1)) * 0.5)
    w1 = jax.random.normal(ks[2], (lib, d, d_h)) * 0.2
    b1 = jnp.zeros((lib, d_h))
    w2 = jax.random.normal(ks[3], (lib, d_h, d)) * 0.2
    b2 = jnp.zeros((lib, d))
    stacks = ops.prepad_switched_weights(w1, b1, w2, b2)
    wi = jax.random.normal(ks[4], (d, d)) * 0.1
    exact_fn = lambda xb: jnp.dot(xb, wi)
    fns = {}
    for be in D.DISPATCH_BACKENDS:
        interp = be in D.PALLAS_BACKENDS
        fns[be] = jax.jit(lambda xx, lg, rv, b=be, ip=interp:
                          D.mcma_dispatch(
                              xx, lg, exact_fn, *stacks, exact_cap=48,
                              invoke_cap=32, backend=b, block_t=32,
                              interpret=ip, weights_prepadded=True,
                              residency=rv)[0])
    for res in ([0, 1, 2], [5, 3, 1], [4, 4, 0]):    # incl. duplicate ids
        rv = jnp.asarray(res, jnp.int32)
        ys = {be: np.asarray(f(x, logits, rv)) for be, f in fns.items()}
        np.testing.assert_array_equal(ys["pallas_fused"], ys["pallas"])
        np.testing.assert_allclose(ys["pallas_fused"], ys["xla"],
                                   rtol=1e-6, atol=1e-6)
    for be, f in fns.items():
        assert_zero_retrace(f, f"{be}: a residency swap")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_vector_io_branches_bit_identical(dtype):
    """The kernel's two static I/O lowerings (vectorized take/.at vs the
    per-row copies staged through f32 scratch) must produce the same bits
    — the compiled-mode branch is what runs on TPU, the vectorized one in
    CI.  bf16 is the served dtype, and the one the f32 staging is for."""
    t, n, d, d_h = 130, 4, 40, 16
    x, _, (w1, b1, w2, b2), _ = _mk_case(jax.random.PRNGKey(3), t, n, d, d_h)
    x, w1, b1, w2, b2 = (a.astype(dtype) for a in (x, w1, b1, w2, b2))
    cls = jax.random.randint(jax.random.PRNGKey(4), (t,), 0, n)
    ys = [np.asarray(ops.switched_apply_fused(
        x, cls, w1, b1, w2, b2, block_t=32, interpret=True, vector_io=vio))
        for vio in (True, False)]
    np.testing.assert_array_equal(ys[0], ys[1])
    np.testing.assert_array_equal(
        ys[0], np.asarray(ops.switched_apply(
            x, cls, w1, b1, w2, b2, block_t=32, interpret=True)))


# ---------------------------------------------------------------------------
# op-count audit: <= 1 standalone activation gather/scatter per layer
# ---------------------------------------------------------------------------

def test_fused_execute_runs_one_activation_pass_per_layer():
    t, n, d, d_h, layers = 128, 3, 32, 16, 3
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(9), t, n, d, d_h)
    stacked = jax.tree.map(
        lambda a: jnp.stack([a * (0.8 + 0.1 * i) for i in range(layers)]), w)
    moves = {}
    for be in D.DISPATCH_BACKENDS:
        interp = be in D.PALLAS_BACKENDS
        plan = D.make_dispatch_plan(logits, exact_cap=64, invoke_cap=48,
                                    backend=be, block_t=32)

        def tick(xx, ip=interp, p=plan):
            def layer(h, ws):
                return D.execute_dispatch(p, h, exact_fn, *ws,
                                          interpret=ip), None
            return jax.lax.scan(layer, xx, stacked)[0]

        g, s = activation_moves(jax.make_jaxpr(tick)(x))
        assert g % layers == 0 and s % layers == 0, (be, g, s)
        moves[be] = (g // layers, s // layers)
    gf, sf = moves["pallas_fused"]
    gu, su = moves["pallas"]
    # fused: only the exact-path capacity buffers remain standalone;
    # unfused additionally pays the class-sort gather + inverse scatter
    assert gf <= 1 and sf <= 1, moves
    assert gf < gu and sf < su, moves


def test_fused_plan_reuse_zero_retrace_across_traced_inputs():
    t, n, d, d_h = 96, 3, 32, 16
    x, logits, w, exact_fn = _mk_case(jax.random.PRNGKey(21), t, n, d, d_h)
    margins = jnp.asarray([0.5, 0.0, -0.5], jnp.float32)
    tier = jnp.arange(t, dtype=jnp.int32) % 3
    plan_fn = jax.jit(lambda lg, mk, tr, mg: D.make_dispatch_plan(
        lg, mk, exact_cap=48, invoke_cap=32, backend="pallas_fused",
        block_t=32, tier=tr, tier_margins=mg))
    exec_fn = jax.jit(lambda p, xx: D.execute_dispatch(
        p, xx, exact_fn, *w, interpret=True))
    mask = jnp.ones((t,), bool)
    for i in range(3):                       # mask/tier/margin changes
        p = plan_fn(logits + 0.1 * i, mask.at[i].set(False),
                    (tier + i) % 3, margins * (1.0 - 0.2 * i))
        jax.block_until_ready(exec_fn(p, x))
    assert_zero_retrace(plan_fn, "a mask/tier/margin change")
    assert_zero_retrace(exec_fn, "a replanned fused execute")


# ---------------------------------------------------------------------------
# model decode: layer + tick scope through the fused backend
# ---------------------------------------------------------------------------

def _decode_cfg(backend, scope):
    cfg = smoke_config(get_config("internlm2-1.8b"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, backend=backend,
        interpret=backend in D.PALLAS_BACKENDS, block_t=16,
        route_scope=scope))


@pytest.mark.parametrize("scope", ["layer", "tick"])
def test_decode_step_fused_backend_both_scopes(scope):
    B = 8
    params = M.init_model(jax.random.PRNGKey(0), _decode_cfg("xla", scope))
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    mask = jnp.asarray([True] * 6 + [False] * 2)
    outs = {}
    for be in D.DISPATCH_BACKENDS:
        cfg = _decode_cfg(be, scope)
        cache = M.init_cache(cfg, B, 32)
        lg, _, m = M.decode(cfg, params, cache, toks, serve=True,
                            collect_metrics=True, row_mask=mask)
        outs[be] = np.asarray(lg)
        assert np.isfinite(float(m["invocation"]))
    np.testing.assert_array_equal(outs["pallas_fused"], outs["pallas"])
    np.testing.assert_allclose(outs["pallas_fused"], outs["xla"],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mesh: (4, 2) — subprocess always, in-process when 8 devices exist
# ---------------------------------------------------------------------------

_MESH = textwrap.dedent("""
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
            block_t=16, route_scope=scope))

    B = 8
    mask = jnp.asarray([True] * 6 + [False] * 2)
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    params = M.init_model(jax.random.PRNGKey(0), cfg_with("xla", "tick"))
    out = {}
    for scope in ("layer", "tick"):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        outs = {}
        for backend in ("xla", "pallas", "pallas_fused"):
            c = cfg_with(backend, scope)
            cache = M.init_cache(c, B, 32)
            with mesh, A.activation_sharding(P(("data",), None, None)):
                lg, _, m = jax.jit(
                    lambda p, ca, t, rm, c_=c: M.decode(
                        c_, p, ca, t, serve=True, collect_metrics=True,
                        row_mask=rm))(params, cache, toks, mask)
            outs[backend] = np.asarray(lg)
        out[scope] = {
            "fused_bitexact_vs_pallas": bool(
                np.array_equal(outs["pallas_fused"], outs["pallas"])),
            "fused_err_vs_xla": float(
                np.abs(outs["pallas_fused"] - outs["xla"]).max()),
        }
    print("RESULT" + json.dumps(out))
""")


def test_fused_mesh_subprocess_4x2():
    r = subprocess.run([sys.executable, "-c", _MESH], capture_output=True,
                       text=True, timeout=600, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.split("RESULT")[1])
    for scope in ("layer", "tick"):
        assert out[scope]["fused_bitexact_vs_pallas"], (scope, out)
        assert out[scope]["fused_err_vs_xla"] < 2e-5, (scope, out)


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI multidevice leg: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@needs_8_devices
def test_fused_sharded_engine_inprocess_8_devices():
    t, n, d, d_h = 256, 4, 32, 16
    x, logits, (w1, b1, w2, b2), _ = _mk_case(
        jax.random.PRNGKey(13), t, n, d, d_h)
    wi = jax.random.normal(jax.random.PRNGKey(14), (d, d)) * 0.1
    exact_fn_p = lambda ep, xb: jnp.dot(xb, ep)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    outs = {}
    for be in D.DISPATCH_BACKENDS:
        interp = be in D.PALLAS_BACKENDS
        y, _ = jax.jit(lambda xx, lg, b=be, ip=interp:
                       D.mcma_dispatch_sharded(
                           mesh, xx, lg, exact_fn_p, wi, w1, b1, w2, b2,
                           exact_cap=16, invoke_cap=12, backend=b,
                           block_t=16, interpret=ip))(x, logits)
        outs[be] = np.asarray(y)
    np.testing.assert_array_equal(outs["pallas_fused"], outs["pallas"])
    np.testing.assert_allclose(outs["pallas_fused"], outs["xla"],
                               rtol=1e-6, atol=1e-6)
