"""Runtime: decode server (continuous batching), gradient compression,
optimizers, straggler monitor."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke_config
from repro.models import model as M
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule
from repro.runtime.server import DecodeServer, Request
from repro.runtime.trainer import StragglerMonitor

jax.config.update("jax_platform_name", "cpu")


def test_server_drains_and_recycles_slots():
    cfg = smoke_config(get_config("internlm2-1.8b"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    server = DecodeServer(cfg, params, batch=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
                    max_new=6) for i in range(5)]   # 5 requests, 2 slots
    for r in reqs:
        server.submit(r)
    stats = server.run_until_drained(max_ticks=500)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 6 for r in reqs)
    assert stats["ticks"] < 500


@pytest.mark.parametrize("platform,backend,interpret", [
    ("cpu", "pallas", True),
    ("cpu", "pallas_fused", True),
    ("tpu", "pallas", False),
    ("tpu", "pallas_fused", False),
    ("gpu", "xla", False),
    ("gpu", "pallas", None),
    ("rocm", "pallas_fused", None),
])
def test_mcma_serve_config_interprets_only_on_cpu(monkeypatch, platform,
                                                  backend, interpret):
    """The Pallas kernels compile on TPU and are interpreted on CPU only;
    on any other platform the serve config raises instead of quietly
    returning an interpret-mode config (``interpret=None`` marks those)."""
    from repro.runtime import steps
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match=platform):
            steps.mcma_serve_config(cfg, backend=backend)
        return
    out = steps.mcma_serve_config(cfg, backend=backend)
    assert out.approx.backend == backend
    assert out.approx.interpret is interpret


def test_server_slot_reset_isolates_requests():
    """A recycled slot must not leak KV state: the same prompt must yield
    the same tokens whether it runs in a fresh server or a recycled slot."""
    cfg = smoke_config(get_config("olmo-1b"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(1, 9, dtype=np.int32)

    fresh = DecodeServer(cfg, params, batch=1, max_len=64)
    r1 = Request(rid=0, prompt=prompt, max_new=5)
    fresh.submit(r1)
    fresh.run_until_drained(200)

    recycled = DecodeServer(cfg, params, batch=1, max_len=64)
    filler = Request(rid=1, prompt=np.ones(3, np.int32), max_new=4)
    r2 = Request(rid=2, prompt=prompt, max_new=5)
    recycled.submit(filler)
    recycled.submit(r2)
    recycled.run_until_drained(200)

    assert r1.out == r2.out, (r1.out, r2.out)


def test_adamw_decreases_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0, 1.5])}
    opt = adamw_init(params)
    step = jnp.zeros((), jnp.int32)
    for i in range(200):
        grads = {"w": 2 * params["w"]}
        params, opt = adamw_update(params, grads, opt, step + i, lr=5e-2,
                                   weight_decay=0.0)
    assert float(jnp.sum(params["w"] ** 2)) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.asarray(s), base_lr=1.0, warmup=10,
                                 total=100)) for s in range(100)]
    assert lrs[0] < lrs[9]                 # warmup rises
    assert max(lrs) == pytest.approx(1.0, rel=1e-2)
    assert lrs[-1] < 0.01                  # decays to ~0


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor()
    flags = [mon.observe(1.0) for _ in range(10)]
    assert not any(flags)
    assert mon.observe(10.0) is True
    assert mon.slow_steps == 1


_COMPRESSION = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import ef_int8_allreduce_tree, init_error_feedback

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pod",))
    # quadratic: each pod sees a different shard of the data
    key = jax.random.PRNGKey(0)
    targets = jax.random.normal(key, (4, 8))
    w0 = jnp.zeros((8,))

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P("pod"), P()),
             out_specs=(P(), P()), check_vma=False)
    def compressed_step(w, tgt, err):
        g = 2 * (w - tgt[0])                     # local gradient
        mean_g, new_err = ef_int8_allreduce_tree({"g": g}, {"g": err},
                                                 "pod")
        return mean_g["g"], new_err["g"]

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P("pod")), out_specs=P(),
             check_vma=False)
    def exact_step(w, tgt):
        g = 2 * (w - tgt[0])
        return jax.lax.pmean(g, "pod")

    # one jitted fori_loop: eager multi-device dispatch costs ~1s/step on
    # host devices, which pushed the subprocess past its timeout.  The
    # carry starts at the (4, 8) shape w takes after the first step (the
    # replicated err broadcasts through the allreduce) so the loop-carry
    # type is stable; values match the eager trajectory exactly.
    @jax.jit
    def run(w0, targets, err0):
        def body(_, c):
            w_c, err, w_e = c
            g_c, err = compressed_step(w_c, targets, err)
            return (w_c - 0.05 * g_c, err,
                    w_e - 0.05 * exact_step(w_e, targets))
        wb = jnp.zeros((4, 8)) + w0
        return jax.lax.fori_loop(0, 300, body, (wb, err0, wb))

    err0 = jnp.zeros((4, 8))                     # per-pod error feedback
    w_c, err, w_e = run(w0, targets, err0)
    opt = jnp.mean(targets, 0)
    out = {"err_compressed": float(jnp.linalg.norm(w_c - opt)),
           "err_exact": float(jnp.linalg.norm(w_e - opt))}
    print("RESULT" + json.dumps(out))
""")


def test_int8_error_feedback_converges():
    r = subprocess.run([sys.executable, "-c", _COMPRESSION],
                       capture_output=True, text=True, timeout=600,
                       # JAX_PLATFORMS pins CPU: without it jax probes the
                       # TPU plugin and stalls ~8min on TPU-less hosts
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    import json
    out = json.loads(r.stdout.split("RESULT")[1])
    assert out["err_exact"] < 1e-3
    assert out["err_compressed"] < 1e-2   # EF keeps quantization unbiased
