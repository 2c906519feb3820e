"""Approximator-library residency: routing over a ``library_size``-wide
head, a traced residency map folding library classes onto resident
slots, off-set fallback to exact, and runtime hot-set swapping.

Pins, per the PR's acceptance criteria:
  * the residency fold's accounting is exact: ``lib_counts`` histograms
    the FULL library demand, off-set rows land in the exact column, and
    ``off_set_exact_rows == class_counts[0] - lib_counts[0]`` — the
    off-set rows are exactly the exact path's extra rows;
  * an identity residency (every library class resident) is bit-for-bit
    the library-less engine — the fold is a pure widening;
  * pallas == xla bit-for-bit at EVERY visited residency set, on one
    device and on the 8-virtual-device (data, model) mesh;
  * promotion/demotion never retraces: one jitted program serves every
    residency vector (jit-cache-size check), at the engine level and
    through a live ``DecodeServer`` whose ResidencyController swapped;
  * the ResidencyController's hysteresis: promotes the hot off-set
    class over the cold resident, ratio + floor gates block thrash;
  * ``train_library`` co-trains ``library_size`` members behind the
    same MCMA interface.
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
from repro.kernels import ops
from repro.models import model as M
from repro.runtime import autotune as AT
from repro.runtime import dispatch as D
from repro.runtime.options import LibrarySpec, ServeOptions

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


def _run(script: str) -> dict:
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.split("RESULT")[1])


def _mk_library_case(key, t, lib, d, d_h):
    """Inputs + library-wide router logits + PREPADDED library stacks."""
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (t, d), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (d, lib + 1)) * 0.5
    w1 = jax.random.normal(ks[2], (lib, d, d_h)) * 0.2
    b1 = jax.random.normal(ks[3], (lib, d_h)) * 0.1
    w2 = jax.random.normal(ks[4], (lib, d_h, d)) * 0.2
    b2 = jax.random.normal(ks[5], (lib, d)) * 0.1
    wi = jax.random.normal(jax.random.fold_in(key, 7), (d, 2 * d)) * 0.1
    wo = jax.random.normal(jax.random.fold_in(key, 8), (2 * d, d)) * 0.1
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    stacks = ops.prepad_switched_weights(w1, b1, w2, b2)
    return x, x @ router, stacks, exact_fn


def _lib_cfg(**over):
    cfg = smoke_config(get_config("internlm2-1.8b"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, library_size=6, **over))


RESIDENCIES = ([0, 1], [2, 5], [4, 0], [3, 2])


# ---------------------------------------------------------------------------
# the residency fold: exact off-set accounting
# ---------------------------------------------------------------------------

def test_residency_fold_accounting_exact():
    t, lib, n, d, d_h = 128, 6, 2, 48, 16
    x, logits, w, exact_fn = _mk_library_case(jax.random.PRNGKey(0), t, lib,
                                              d, d_h)
    res = jnp.asarray([4, 1], jnp.int32)
    _, s = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=t,
                           invoke_cap=t, backend="xla",
                           weights_prepadded=True, residency=res)
    s = jax.tree.map(np.asarray, s)
    # full-library histogram covers every row once
    assert s["lib_counts"].shape == (lib + 1,)
    assert s["lib_counts"].sum() == t
    # resident slots serve their library class's demand exactly
    for slot, c in enumerate([4, 1]):
        assert s["class_counts"][slot + 1] == s["lib_counts"][c + 1]
    # off-set rows are EXACTLY the exact path's extra rows
    off = sum(s["lib_counts"][c + 1] for c in range(lib) if c not in (4, 1))
    assert s["off_set_exact_rows"] == off
    assert s["class_counts"][0] == s["lib_counts"][0] + off
    # library-less stats alias: no residency -> lib_counts == class_counts
    n_all = lib
    _, s0 = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=t,
                            invoke_cap=t, backend="xla",
                            weights_prepadded=True,
                            residency=jnp.arange(n_all, dtype=jnp.int32))
    s0 = jax.tree.map(np.asarray, s0)
    assert s0["off_set_exact_rows"] == 0


def test_identity_residency_is_library_less_engine():
    """Every library class resident, in order: output and every stat must
    be bit-identical to running the same stacks without a residency map
    — the fold is a pure widening."""
    t, lib, d, d_h = 96, 4, 48, 16
    x, logits, w, exact_fn = _mk_library_case(jax.random.PRNGKey(1), t, lib,
                                              d, d_h)
    kw = dict(exact_cap=t // 2, invoke_cap=max(t // 8, 1), backend="xla",
              weights_prepadded=True)
    y0, s0 = D.mcma_dispatch(x, logits, exact_fn, *w, **kw)
    y1, s1 = D.mcma_dispatch(x, logits, exact_fn, *w,
                             residency=jnp.arange(lib, dtype=jnp.int32),
                             **kw)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(s0["class_counts"]),
                                  np.asarray(s1["class_counts"]))
    np.testing.assert_array_equal(np.asarray(s0["dispatched"]),
                                  np.asarray(s1["dispatched"]))
    np.testing.assert_array_equal(np.asarray(s1["lib_counts"]),
                                  np.asarray(s1["class_counts"]))
    assert int(np.asarray(s1["off_set_exact_rows"])) == 0


# ---------------------------------------------------------------------------
# pallas == xla at every visited residency set; swaps never retrace
# ---------------------------------------------------------------------------

def test_residency_pallas_matches_xla_every_set():
    t, lib, d, d_h = 128, 6, 48, 16
    x, logits, w, exact_fn = _mk_library_case(jax.random.PRNGKey(2), t, lib,
                                              d, d_h)
    for res in RESIDENCIES:
        outs, stats = {}, {}
        for backend in ("xla", "pallas"):
            y, s = D.mcma_dispatch(
                x, logits, exact_fn, *w, exact_cap=t // 2,
                invoke_cap=max(t // 6, 1), backend=backend, block_t=32,
                interpret=backend == "pallas", weights_prepadded=True,
                residency=jnp.asarray(res, jnp.int32))
            outs[backend] = np.asarray(y)
            stats[backend] = jax.tree.map(np.asarray, s)
        np.testing.assert_array_equal(outs["pallas"], outs["xla"],
                                      err_msg=f"residency={res}")
        for k in ("class_counts", "dispatched", "lib_counts",
                  "off_set_exact_rows"):
            np.testing.assert_array_equal(stats["pallas"][k],
                                          stats["xla"][k], err_msg=str(res))


def test_swap_is_traced_never_retraces():
    """One jitted program serves every residency vector — a promotion is
    a new traced value through the SAME compiled step."""
    t, lib, d, d_h = 64, 6, 32, 8
    x, logits, w, exact_fn = _mk_library_case(jax.random.PRNGKey(3), t, lib,
                                              d, d_h)
    fn = jax.jit(lambda res: D.mcma_dispatch(
        x, logits, exact_fn, *w, exact_cap=t // 2, invoke_cap=t // 4,
        backend="xla", weights_prepadded=True, residency=res))
    seen = []
    for res in RESIDENCIES:
        _, s = fn(jnp.asarray(res, jnp.int32))
        seen.append(float(s["off_set_exact_rows"]))
    assert_zero_retrace(fn, "a residency swap")
    assert len(set(seen)) > 1, "residency had no effect on routing"


# ---------------------------------------------------------------------------
# decode path: the tick/layer scopes, metrics export
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_decode_residency_pallas_matches_xla(route_scope):
    b = 6
    params = M.init_model(jax.random.PRNGKey(0), _lib_cfg())
    mask = jnp.asarray([True] * 5 + [False])
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    res = jnp.asarray([3, 5], jnp.int32)
    outs, ms = {}, {}
    for be, kw in (("xla", {}),
                   ("pallas", dict(interpret=True, block_t=16))):
        cfg = _lib_cfg(backend=be, route_scope=route_scope, **kw)
        cache = M.init_cache(cfg, b, 32)
        lg, _, m = M.decode(cfg, params, cache, toks, serve=True,
                            collect_metrics=True, row_mask=mask,
                            residency=res)
        outs[be], ms[be] = np.asarray(lg), jax.tree.map(np.asarray, m)
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(ms["pallas"]["lib_counts"],
                                  ms["xla"]["lib_counts"])
    m = ms["xla"]
    assert m["lib_counts"].shape == (7,)        # library_size + 1
    assert float(m["lib_counts"].sum()) == 5.0  # active rows only
    assert m["off_set_exact_rows"] >= 0


# ---------------------------------------------------------------------------
# ResidencyController: hysteresis law
# ---------------------------------------------------------------------------

def _spec(**over):
    kw = dict(library_size=6, n_resident=2, observe_window=1, cooldown=0,
              ema=1.0)
    kw.update(over)
    return LibrarySpec(**kw)


def test_controller_promotes_hot_off_set_class():
    ctrl = AT.ResidencyController(_spec())
    # library class 3 (lib_counts entry 4) dominates; residents 0/1 cold
    lib = np.asarray([10.0, 2.0, 1.0, 0.0, 30.0, 0.0, 0.0])
    res = ctrl.observe({"lib_counts": lib})
    assert 3 in res
    assert ctrl.history[0].promoted == 3
    assert ctrl.history[0].demoted in (0, 1)


def test_controller_ratio_gate_blocks_borderline_thrash():
    """A challenger near parity with the coldest resident never swaps."""
    ctrl = AT.ResidencyController(_spec(promote_margin=1.5))
    # cold resident share 10/93 (below the demote floor, so ONLY the
    # ratio gate stands); challenger 13/93 < 1.5x that — no swap
    lib = np.asarray([60.0, 10.0, 10.0, 13.0, 0.0, 0.0, 0.0])
    for _ in range(8):
        res = ctrl.observe({"lib_counts": lib})
    assert res == (0, 1)
    assert not ctrl.history


def test_controller_floor_gate_protects_busy_resident():
    """A resident above the demote floor is never demoted, whatever is
    knocking."""
    ctrl = AT.ResidencyController(_spec(demote_margin=0.25))
    # cold resident holds 26% of traffic (above the floor); the
    # challenger's 45% clears the ratio gate — floor alone must block
    lib = np.asarray([0.0, 26.0, 29.0, 45.0, 0.0, 0.0, 0.0])
    for _ in range(8):
        res = ctrl.observe({"lib_counts": lib})
    assert res == (0, 1)
    assert not ctrl.history


def test_controller_cooldown_spaces_swaps():
    ctrl = AT.ResidencyController(_spec(observe_window=1, cooldown=3))
    hot = np.zeros(7)
    hot[3] = 50.0           # library class 2, off-set
    hot[1] = 1.0
    for _ in range(4):
        ctrl.observe({"lib_counts": hot})
    assert len(ctrl.history) == 1          # cooldown swallowed the rest


def test_library_spec_validation():
    with pytest.raises(AssertionError):
        LibrarySpec(library_size=2, n_resident=4)
    with pytest.raises(AssertionError):
        LibrarySpec(library_size=4, n_resident=2, promote_margin=0.5)
    with pytest.raises(AssertionError):
        LibrarySpec(library_size=4, n_resident=2, start=(0, 9))
    assert LibrarySpec(4, 2).initial_residency() == (0, 1)
    assert LibrarySpec(4, 2, start=(3, 1)).initial_residency() == (3, 1)


# ---------------------------------------------------------------------------
# server end to end: swaps happen, zero retraces, stats surface
# ---------------------------------------------------------------------------

def test_server_library_swaps_without_retrace():
    from repro.runtime.server import DecodeServer, Request
    cfg = _lib_cfg(backend="xla")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    srv = DecodeServer(cfg, params, options=ServeOptions(
        batch=4, max_len=64, use_mcma_dispatch=True, prefill_chunk=4,
        library=LibrarySpec(library_size=6, n_resident=2,
                            observe_window=2, cooldown=2)))
    assert srv.cfg.approx.n_approx == 2          # serving slots
    assert srv.cfg.approx.library_size == 6
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, 6)
                    .astype(np.int32), max_new=6) for i in range(10)]
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained(max_ticks=400)
    assert all(r.done for r in reqs)
    # the drain summary carries the library ledger
    lib = stats["lib_routed_per_class"]
    assert len(lib) == 7
    assert stats["off_set_exact_rows"] >= 0
    summ = stats["residency"]
    assert len(summ["final_residency"]) == 2
    # swapping (if any happened) cost ZERO retraces: the decode and chunk
    # steps each compiled exactly once
    assert_zero_retrace(srv.decode, "a live residency swap (decode step)")
    assert_zero_retrace(srv.chunk, "a live residency swap (chunk step)")
    # off-set rows reconcile against the full-library demand histogram
    resident_demand = sum(lib[c + 1] for c in summ["final_residency"])
    assert stats["off_set_exact_rows"] <= sum(lib[1:])


def test_server_library_requires_matching_config():
    from repro.runtime.server import DecodeServer
    cfg = _lib_cfg()
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(AssertionError, match="library_size"):
        DecodeServer(cfg, params, options=ServeOptions(
            use_mcma_dispatch=True,
            library=LibrarySpec(library_size=4, n_resident=2)))
    with pytest.raises(AssertionError, match="dispatch engine"):
        DecodeServer(cfg, params, options=ServeOptions(
            library=LibrarySpec(library_size=6, n_resident=2),
            use_mcma_dispatch=False))


# ---------------------------------------------------------------------------
# train_library: error-clustered co-training at library scale
# ---------------------------------------------------------------------------

def test_train_library_smoke():
    from repro.apps.registry import get_app, make_dataset
    from repro.core.mcma import train_library
    app = get_app("fft")
    x, y, xt, yt = make_dataset(app, jax.random.PRNGKey(0), 256, 128)
    m = train_library(app, jax.random.PRNGKey(1), x, y, library_size=4,
                      iters=2, epochs=40, lr=1e-2)
    assert m.n_approx == 4
    assert len(m.history) == 2
    cls = np.asarray(m.classify(xt))
    assert cls.min() >= 0 and cls.max() <= 4    # library classes + nC


# ---------------------------------------------------------------------------
# mesh: residency on 8 virtual devices (subprocess)
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
            block_t=16, route_scope=scope, library_size=6))

    B = 8
    mask = jnp.asarray([True] * 6 + [False] * 2)
    toks = jnp.arange(1, B + 1, dtype=jnp.int32)[:, None]
    params = M.init_model(jax.random.PRNGKey(0), cfg_with("xla", "tick"))
    out = {}
    for scope in ("layer", "tick"):
        per_res = {}
        for res in ([0, 1], [4, 2]):
            resv = jnp.asarray(res, jnp.int32)
            cfg = cfg_with("xla", scope)
            cache = M.init_cache(cfg, B, 32)
            _, _, m1 = M.decode(cfg, params, cache, toks, serve=True,
                                collect_metrics=True, row_mask=mask,
                                residency=resv)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            outs, libs = {}, {}
            for backend in ("xla", "pallas"):
                c = cfg_with(backend, scope)
                with mesh, A.activation_sharding(P(("data",), None, None)):
                    lg, _, m = jax.jit(
                        lambda p, ca, t, rm, rv, c_=c: M.decode(
                            c_, p, ca, t, serve=True, collect_metrics=True,
                            row_mask=rm, residency=rv))(
                        params, cache, toks, mask, resv)
                outs[backend] = np.asarray(lg)
                libs[backend] = np.asarray(m["lib_counts"]).tolist()
            per_res[str(res)] = {
                "pallas_bitexact_vs_xla": bool(
                    np.array_equal(outs["pallas"], outs["xla"])),
                "lib_counts": libs,
                "single_lib_counts":
                    np.asarray(m1["lib_counts"]).tolist(),
            }
        out[scope] = per_res
    print("RESULT" + json.dumps(out))
""")


def test_residency_mesh_subprocess_8_virtual_devices():
    out = _run(_MESH)
    for scope in ("layer", "tick"):
        for res, o in out[scope].items():
            assert o["pallas_bitexact_vs_xla"], (scope, res)
            assert o["lib_counts"]["pallas"] == o["lib_counts"]["xla"], \
                (scope, res)
    # tick scope routes once from the drift-free embedding: the psum'd
    # full-library histogram equals the single-device one exactly
    for res, o in out["tick"].items():
        for be in ("xla", "pallas"):
            assert o["lib_counts"][be] == o["single_lib_counts"], (be, res)


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI multidevice leg: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@needs_8_devices
@pytest.mark.parametrize("route_scope", ["layer", "tick"])
def test_residency_mesh_inprocess(route_scope):
    """CI multidevice leg: pallas == xla on the (4, 2) mesh at every
    visited residency set, and swaps through one jitted program never
    retrace even under shard_map."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import activations as A
    b = 8
    mask = jnp.asarray([True] * 6 + [False] * 2)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    params = M.init_model(jax.random.PRNGKey(0), _lib_cfg())
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    fns = {}
    for be, kw in (("xla", {}),
                   ("pallas", dict(interpret=True, block_t=16))):
        c = _lib_cfg(backend=be, route_scope=route_scope, **kw)
        fns[be] = jax.jit(lambda p, ca, t, rm, rv, c_=c: M.decode(
            c_, p, ca, t, serve=True, collect_metrics=True,
            row_mask=rm, residency=rv))
    for res in ([0, 1], [5, 3], [2, 4]):
        resv = jnp.asarray(res, jnp.int32)
        outs, libs = {}, {}
        for be in ("xla", "pallas"):
            cache = M.init_cache(_lib_cfg(), b, 32)
            with mesh, A.activation_sharding(P(("data",), None, None)):
                lg, _, m = fns[be](params, cache, toks, mask, resv)
            outs[be] = np.asarray(lg)
            libs[be] = np.asarray(m["lib_counts"])
        np.testing.assert_array_equal(outs["pallas"], outs["xla"],
                                      err_msg=str(res))
        np.testing.assert_array_equal(libs["pallas"], libs["xla"])
        assert float(libs["xla"].sum()) == 6.0   # active rows only
    for be in ("xla", "pallas"):
        assert_zero_retrace(fns[be],
                            f"{be}: a residency swap under the mesh")
