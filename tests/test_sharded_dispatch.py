"""Shard_map-native MCMA dispatch (runtime/dispatch.py): sharded engine vs
the single-device per-shard reference, psum-reduced invoke_stats vs
single-device totals, the manual ApproxFFN serve path through the engine,
and the mesh DecodeServer end to end.

Two flavors per invariant:
  * in-process tests that need >= 8 jax devices — skipped on a plain run,
    exercised by the CI multidevice leg / `make test-multidevice`
    (XLA_FLAGS=--xla_force_host_platform_device_count=8);
  * subprocess tests (the test_sharding.py pattern) that force 8 virtual
    CPU devices themselves, so the shard_map paths run on EVERY pytest
    invocation, not only on the multidevice leg.
"""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


def _run(script: str) -> dict:
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.split("RESULT")[1])


# ---------------------------------------------------------------------------
# Shared case builder (also used inside the subprocess scripts via repr)
# ---------------------------------------------------------------------------

_CASE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np

    T, N, D, DH, BLOCK, DEVS = 256, 3, 64, 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (T, D), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (D, N + 1)) * 0.5
    w1 = jax.random.normal(ks[2], (N, D, DH)) * 0.2
    b1 = jax.random.normal(ks[3], (N, DH)) * 0.1
    w2 = jax.random.normal(ks[4], (N, DH, D)) * 0.2
    b2 = jax.random.normal(ks[5], (N, D)) * 0.1
    wi = jax.random.normal(ks[6], (D, 2 * D)) * 0.1
    wo = jax.random.normal(ks[7], (2 * D, D)) * 0.1
    logits = x @ router
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    exact_fn_p = lambda ep, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, ep[0])),
                                        ep[1])
    TL = T // DEVS
    EC, IC = TL // 2, max(int(TL * 0.4), 1)
""")


def _sharded_vs_reference(backend: str) -> dict:
    """Runs inside THIS process (needs >= 8 devices) — returns the same
    payload shape as the subprocess variant."""
    from repro.runtime import dispatch as D
    exec_ns: dict = {}
    exec(compile(_CASE, "<case>", "exec"), exec_ns)
    T, N, BLOCK, DEVS, TL = (exec_ns[k] for k in
                             ("T", "N", "BLOCK", "DEVS", "TL"))
    EC, IC = exec_ns["EC"], exec_ns["IC"]
    x, logits = exec_ns["x"], exec_ns["logits"]
    w = (exec_ns["w1"], exec_ns["b1"], exec_ns["w2"], exec_ns["b2"])
    exact_fn, exact_fn_p = exec_ns["exact_fn"], exec_ns["exact_fn_p"]
    wi, wo = exec_ns["wi"], exec_ns["wo"]

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((DEVS,), ("data",))
    y_sh, s_sh = jax.jit(lambda xx, lg: D.mcma_dispatch_sharded(
        mesh, xx, lg, exact_fn_p, (wi, wo), *w, exact_cap=EC, invoke_cap=IC,
        backend=backend, block_t=BLOCK, interpret=True))(x, logits)

    # single-device reference: each shard's rows dispatched independently
    # with the same per-shard capacities, stats summed
    ys, acc = [], None
    for i in range(DEVS):
        yi, si = D.mcma_dispatch(
            x[i * TL:(i + 1) * TL], logits[i * TL:(i + 1) * TL], exact_fn,
            *w, exact_cap=EC, invoke_cap=IC, backend=backend, block_t=BLOCK,
            interpret=True)
        ys.append(np.asarray(yi))
        si = jax.tree.map(np.asarray, si)
        acc = si if acc is None else {
            k: acc[k] + si[k] for k in
            ("class_counts", "dispatched", "dropped", "executed_rows",
             "padding_rows")}
    y_ref = np.concatenate(ys)
    # full-batch single call: routing (class_counts) is row-wise, so the
    # sharded totals must equal the unsharded ones exactly
    _, s_full = D.mcma_dispatch(x, logits, exact_fn, *w, exact_cap=T // 2,
                                invoke_cap=int(T * 0.4), backend="xla")
    return {"y_sh": np.asarray(y_sh), "y_ref": y_ref,
            "s_sh": jax.tree.map(np.asarray, s_sh), "s_ref": acc,
            "full_counts": np.asarray(s_full["class_counts"]), "T": T}


def _assert_sharded_payload(p):
    np.testing.assert_array_equal(p["y_sh"], p["y_ref"])  # bit-for-bit
    for k in ("class_counts", "dispatched", "dropped", "executed_rows",
              "padding_rows"):
        np.testing.assert_array_equal(p["s_sh"][k], p["s_ref"][k])
    # routing stats are global: identical to a full-batch single call
    np.testing.assert_array_equal(p["s_sh"]["class_counts"],
                                  p["full_counts"])
    assert float(p["s_sh"]["invocation"]) == pytest.approx(
        1.0 - p["full_counts"][0] / p["T"], abs=1e-6)
    assert int(p["s_sh"]["class_counts"].sum()) == p["T"]


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI multidevice leg: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@needs_8_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_dispatch_matches_single_device_inprocess(backend):
    _assert_sharded_payload(_sharded_vs_reference(backend))


@needs_8_devices
def test_sharded_pallas_bitexact_vs_xla_oracle_inprocess():
    """Acceptance: sharded pallas output == sharded xla oracle bit-for-bit
    (CPU f32, interpret mode), stats identical."""
    px = _sharded_vs_reference("xla")
    pp = _sharded_vs_reference("pallas")
    np.testing.assert_array_equal(pp["y_sh"], px["y_sh"])
    for k in ("class_counts", "dispatched", "dropped"):
        np.testing.assert_array_equal(pp["s_sh"][k], px["s_sh"][k])


# ---------------------------------------------------------------------------
# Subprocess variants: run on every pytest invocation (1-device main proc)
# ---------------------------------------------------------------------------

_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime import dispatch as DS
""") + _CASE + textwrap.dedent("""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((DEVS,), ("data",))
    out = {}
    for backend in ("xla", "pallas"):
        y_sh, s_sh = jax.jit(lambda xx, lg, be=backend:
            DS.mcma_dispatch_sharded(
                mesh, xx, lg, exact_fn_p, (wi, wo), w1, b1, w2, b2,
                exact_cap=EC, invoke_cap=IC, backend=be, block_t=BLOCK,
                interpret=True))(x, logits)
        ys, counts, disp, dropped = [], 0, 0, 0
        for i in range(DEVS):
            yi, si = DS.mcma_dispatch(
                x[i*TL:(i+1)*TL], logits[i*TL:(i+1)*TL], exact_fn,
                w1, b1, w2, b2, exact_cap=EC, invoke_cap=IC,
                backend=backend, block_t=BLOCK, interpret=True)
            ys.append(np.asarray(yi))
            counts = counts + np.asarray(si["class_counts"])
            disp = disp + np.asarray(si["dispatched"])
            dropped = dropped + int(si["dropped"])
        out[backend] = {
            "bitexact_vs_ref": bool(np.array_equal(np.asarray(y_sh),
                                                   np.concatenate(ys))),
            "counts_match": bool(np.array_equal(
                np.asarray(s_sh["class_counts"]), counts)),
            "disp_match": bool(np.array_equal(
                np.asarray(s_sh["dispatched"]), disp)),
            "dropped_match": int(s_sh["dropped"]) == dropped,
            "counts_sum": int(np.asarray(s_sh["class_counts"]).sum()),
            "invocation": float(s_sh["invocation"]),
            "y": np.asarray(y_sh).tolist(),
        }
    out["pallas_bitexact_vs_xla"] = bool(np.array_equal(
        np.asarray(out["pallas"]["y"]), np.asarray(out["xla"]["y"])))
    for be in ("xla", "pallas"):
        del out[be]["y"]
    # full-batch routing reference
    _, s_full = DS.mcma_dispatch(x, logits, exact_fn, w1, b1, w2, b2,
                                exact_cap=T // 2, invoke_cap=int(T * 0.4),
                                backend="xla")
    out["full_invocation"] = float(s_full["invocation"])
    print("RESULT" + json.dumps(out))
""")


def test_sharded_dispatch_subprocess_8_virtual_devices():
    out = _run(_SHARDED)
    for be in ("xla", "pallas"):
        assert out[be]["bitexact_vs_ref"], be
        assert out[be]["counts_match"], be
        assert out[be]["disp_match"], be
        assert out[be]["dropped_match"], be
        assert out[be]["counts_sum"] == 256
    assert out["pallas_bitexact_vs_xla"]
    assert out["xla"]["invocation"] == pytest.approx(
        out["full_invocation"], abs=1e-6)


_APPROX_MANUAL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config, smoke_config
    from repro.models.approx_ffn import approx_ffn_fwd, init_approx_ffn
    from repro.sharding import activations as A

    def cfg_with(backend):
        # full capacities: per-shard ranking and global ranking then keep
        # exactly the same rows, so the mesh output must equal the
        # single-device output (up to the TP psum's fp reassociation)
        cfg = smoke_config(get_config("internlm2-1.8b"))
        return dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, enable=True, backend=backend, interpret=True,
            block_t=16, exact_frac=1.0, invoke_frac=1.0))

    cfg = cfg_with("xla")
    p = init_approx_ffn(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, cfg.d_model),
                          jnp.float32) * 0.5

    # single-device engine reference (same per-shard capacities emerge
    # because routing is identical; generous caps avoid drop divergence)
    y1, a1 = approx_ffn_fwd(cfg, p, x, serve=True)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    xs = jax.device_put(x, NamedSharding(mesh, P(("data",), None, None)))
    out = {}
    ys = {}
    for backend in ("xla", "pallas"):
        c = cfg_with(backend)
        with mesh, A.activation_sharding(P(("data",), None, None)):
            y, a = jax.jit(lambda p_, x_, c_=c: approx_ffn_fwd(
                c_, p_, x_, serve=True))(p, xs)
        st = jax.tree.map(np.asarray, a["invoke_stats"])
        ys[backend] = np.asarray(y)
        out[backend] = {
            "counts": st["class_counts"].tolist(),
            "counts_sum": int(st["class_counts"].sum()),
            "invocation": float(a["invocation"]),
            "max_diff_vs_single": float(np.abs(np.asarray(y)
                                               - np.asarray(y1)).max()),
        }
    out["pallas_bitexact_vs_xla"] = bool(np.array_equal(ys["pallas"],
                                                        ys["xla"]))
    out["single_counts"] = np.asarray(
        a1["invoke_stats"]["class_counts"]).tolist()
    out["single_invocation"] = float(a1["invocation"])
    print("RESULT" + json.dumps(out))
""")


def test_approx_ffn_manual_serve_through_engine():
    """The distributed ApproxFFN serve path runs the SAME mcma_dispatch
    engine under shard_map: routing stats equal the single-device run
    exactly, pallas == xla bit-for-bit on the mesh, and the TP exact path
    matches single-device to fp tolerance (psum reorders the d_ff sum)."""
    out = _run(_APPROX_MANUAL)
    assert out["pallas_bitexact_vs_xla"]
    for be in ("xla", "pallas"):
        assert out[be]["counts"] == out["single_counts"], out
        assert out[be]["counts_sum"] == 8 * 16
        assert out[be]["invocation"] == pytest.approx(
            out["single_invocation"], abs=1e-6)
        assert out[be]["max_diff_vs_single"] < 1e-4, out
    print("RESULT ok")


_SERVER_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, numpy as np
    from repro.configs.registry import get_config, smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.runtime.server import DecodeServer, Request

    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(1, 9, dtype=np.int32)

    outs = []
    for mesh in (None, make_host_mesh(data=4, model=2)):
        srv = DecodeServer(cfg, params, batch=4, max_len=64,
                           use_mcma_dispatch=True, mesh=mesh)
        r = Request(rid=0, prompt=prompt, max_new=6)
        srv.submit(r)
        stats = srv.run_until_drained(200)
        outs.append({"out": r.out, "rate": stats["invocation_rate"],
                     "done": r.done})
    print("RESULT" + json.dumps({"single": outs[0], "mesh": outs[1]}))
""")


def test_decode_server_mesh_matches_single_device_tokens():
    """A DecodeServer on a (4, 2) mesh of 8 virtual devices must emit the
    same greedy tokens as the single-device server and report a sane
    psum-reduced invocation rate."""
    out = _run(_SERVER_MESH)
    assert out["mesh"]["done"] and out["single"]["done"]
    assert out["mesh"]["out"] == out["single"]["out"], out
    assert 0.0 <= out["mesh"]["rate"] <= 1.0


# ---------------------------------------------------------------------------
# Spec helpers (no devices needed)
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape)


def test_dispatch_specs_shapes():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules as R
    mesh = FakeMesh((8,), ("data",))
    specs = R.mcma_dispatch_specs(mesh)
    assert len(specs["in"]) == 7 and len(specs["out"]) == 2
    assert specs["in"][0] == P(("data",), None)
    assert specs["out"][1] == P()
    # multi-pod: rows shard over the DP meta-axis
    mesh3 = FakeMesh((2, 4, 2), ("pod", "data", "model"))
    assert R.mcma_dispatch_specs(mesh3)["in"][0] == P(("pod", "data"), None)
    a = R.approx_serve_specs(mesh3, gated=True)
    assert a["in"][0]["ffn"]["w_gate"] == P(("pod", "data"), "model")
    m = R.moe_manual_specs(mesh3, gated=False)
    assert "w_gate" not in m["in"][0]
    assert m["in"][0]["w_in"] == P("model", ("pod", "data"), None)


def test_capacity_slot_helpers_roundtrip():
    """The shared grouped-dispatch primitives: sort -> slots -> scatter ->
    gather must reproduce per-class arrival order with drops zeroed."""
    from repro.runtime import dispatch as D
    cls = jnp.asarray([2, 0, 1, 0, 2, 2, 0, 1], jnp.int32)
    xs = jnp.arange(8, dtype=jnp.float32)[:, None] + 1.0
    order, cls_sorted, rank, counts = D.class_sort_ranks(cls, 3)
    np.testing.assert_array_equal(np.asarray(counts), [3, 2, 3])
    keep, slot = D.capacity_slots(cls_sorted, rank, 2, n_local=3)
    buf = D.scatter_rows(xs[order], slot, keep, 6)
    # class-major, arrival order, capacity 2: rows 1,3 | 2,7 | 0,4
    np.testing.assert_array_equal(np.asarray(buf[:, 0]),
                                  [2, 4, 3, 8, 1, 5])
    y = D.gather_rows(buf, slot, keep)
    got = np.zeros(8, np.float32)
    got[np.asarray(order)] = np.asarray(y[:, 0])
    # rows 5 (class 2) and 6 (class 0) are rank 2 >= cap -> dropped
    want = np.asarray([1, 2, 3, 4, 5, 0, 0, 8], np.float32)
    np.testing.assert_array_equal(got, want)
