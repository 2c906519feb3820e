"""Bring-up smoke run on a TPU: internlm2-1.8b at its published widths,
served through ``DecodeServer`` with the Pallas dispatch kernels compiled.

    python chip_smoke.py              # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4    # the (data=2, model=2) mesh server
                                      # against the one-chip server only

One chip: (a) refuse anything but a TPU; (b) drain eight requests with
prompts of 16 to 300 tokens through the paged, chunked-prefill, tick-scope
MCMA server on the ``pallas`` kernel; (c) the same through
``pallas_fused``; (d) one prefill-chunk step and one decode step from one
cache state under ``xla``, ``pallas`` and ``pallas_fused``: equal dispatch
counts, logits within ``LOGIT_TOL``; (e) ``tpu_custom_call`` in both
compiled Pallas decode steps, and no interpret mode in the served config.

Weights are random, made from ``--seed``.  Times printed here are set-up
(compilation) or whole-drain wall times, never speed measurements.  The
last line of standard output is one JSON object naming the device; any
failed check exits non-zero before it is printed.  Everything runs in this
one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"
BATCH, MAX_LEN, PAGE, CHUNK, MAX_NEW = 8, 1024, 16, 16, 16
PROMPT_LENS = (16, 56, 97, 137, 178, 218, 259, 300)
BACKENDS = ("xla", "pallas", "pallas_fused")
COUNT_KEYS = ("class_counts", "dispatched", "dropped_rows")
# Logit agreement between different arithmetic, as a fraction of the
# reference logits' largest magnitude.  The backends run the same bf16
# math but round at different points (the XLA oracle keeps the
# approximator hidden layer in bf16, the kernels in f32 up to the tanh;
# the mesh reduces the tensor-parallel FFN in pieces), and bf16 carries
# 8 significand bits: a relative rounding step of 2**-8.  Twenty-four
# residual layers compound that to a few per cent at most; a wrong row,
# class or weight block moves logits by their own order (100 per cent).
LOGIT_TOL = 0.05

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's own tracing, lowering and compile durations: set-up
    time, reported apart from any wall time."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == _COMPILE_EVENTS[-1]


def served_config(arch: str = ARCH):
    """The published config with the MCMA ApproxFFN on, nothing cut."""
    from repro.configs.registry import get_config
    cfg = get_config(arch)
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True))


def init_params(cfg, seed: int):
    import jax
    from repro.models import model as M
    params = jax.jit(functools.partial(M.init_model, cfg=cfg))(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def serve_options(backend: str, mesh=None):
    from repro.runtime.options import ServeOptions
    return ServeOptions(batch=BATCH, max_len=MAX_LEN, use_mcma_dispatch=True,
                        route_scope="tick", kv_page_size=PAGE,
                        prefill_chunk=CHUNK, backend=backend, mesh=mesh)


def make_requests(vocab: int, seed: int):
    from repro.runtime.server import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new=MAX_NEW) for i, n in enumerate(PROMPT_LENS)]


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def drain(server, vocab: int, seed: int, label: str, clock: CompileClock):
    """Serve the request set to completion and check every request."""
    reqs = make_requests(vocab, seed)
    for r in reqs:
        server.submit(r)
    c0, n0 = clock.seconds, clock.compiles
    t0 = time.perf_counter()
    stats = server.run_until_drained()
    wall = time.perf_counter() - t0
    bad = [r.rid for r in reqs if not r.done or r.aborted]
    assert not bad, f"{label}: requests not done or aborted: {bad}"
    assert stats["undrained_queued"] == 0 and \
        stats["undrained_inflight"] == 0, stats
    for r in reqs:
        assert len(r.out) == r.max_new, (r.rid, len(r.out))
        assert all(0 <= t < vocab for t in r.out), (r.rid, r.out)
    print(f"[{label}] drained {len(reqs)}/{len(reqs)} requests "
          f"(prompts {min(PROMPT_LENS)}-{max(PROMPT_LENS)} tokens, "
          f"max_new {MAX_NEW}): {stats['ticks']} ticks "
          f"({stats['prefill_ticks']} prefill), served invocation rate "
          f"{stats['served_invocation_rate']!r}, dropped_frac "
          f"{stats['dropped_frac']!r}, page_hwm {stats['page_hwm']} of "
          f"{server.n_pages} pages")
    print(f"[{label}] set-up: {clock.seconds - c0:.1f} s compiling "
          f"({clock.compiles - n0} programs) inside a drain of "
          f"{wall:.1f} s wall — compile time, not speed")
    return stats


def _host(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def one_step_each(servers: dict, params: dict, cfg, seed: int):
    """From one cache state and one input, run one prefill-chunk step and
    then one decode step on every server; return
    ``{name: (logits, chunk_metrics, decode_metrics)}``.  Each server
    gets the same state placed like its own cache."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.runtime import steps
    n_pages = BATCH * MAX_LEN // PAGE
    state = M.init_cache(cfg, BATCH, MAX_LEN, page_size=PAGE,
                         kv_pages=n_pages)
    state["block_table"] = jnp.arange(n_pages, dtype=jnp.int32) \
        .reshape(BATCH, -1)
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, CHUNK)), jnp.int32)
    n_valid = jnp.asarray([CHUNK, CHUNK, 9, CHUNK, 3, CHUNK, 12, 1],
                          jnp.int32)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, 1)), jnp.int32)
    mask = jnp.ones((BATCH,), bool)
    out = {}
    for name, srv in servers.items():
        cache = jax.tree.map(   # a copy: the step donates its cache
            lambda a, ref: jax.device_put(a, ref.sharding, may_alias=False),
            state, srv.cache)
        with steps.serve_mesh_context(srv.mesh):
            cache, m_chunk = srv.chunk(params[name], cache, toks, n_valid)
            logits, cache, m_dec = srv.decode(params[name], cache, nxt, mask)
        out[name] = (np.asarray(logits, np.float32), _host(m_chunk),
                     _host(m_dec))
        del cache
    return out


def compare(results: dict, ref: str, label: str):
    """Dispatch counts equal exactly; logits within LOGIT_TOL."""
    logits_ref, chunk_ref, dec_ref = results[ref]
    scale = float(np.max(np.abs(logits_ref)))
    assert np.isfinite(logits_ref).all() and scale > 0, scale
    for name, (logits, m_chunk, m_dec) in results.items():
        if name == ref:
            continue
        for step, a, b in (("chunk", m_chunk, chunk_ref),
                           ("decode", m_dec, dec_ref)):
            for k in COUNT_KEYS:
                assert np.array_equal(a[k], b[k]), \
                    (label, name, step, k, a[k], b[k])
        assert np.isfinite(logits).all(), (label, name)
        diff = float(np.max(np.abs(logits - logits_ref)))
        agree = float(np.mean(np.argmax(logits, -1)
                              == np.argmax(logits_ref, -1)))
        print(f"[{label}] {name} vs {ref}: max |logit diff| {diff!r} "
              f"({diff / scale!r} of max |logit| {scale!r}; limit "
              f"{LOGIT_TOL}), greedy-token agreement {agree!r}, "
              f"class_counts/dispatched/dropped equal in both steps")
        assert diff <= LOGIT_TOL * scale, (label, name, diff, scale)
    print(f"[{label}] {ref} chunk class_counts "
          f"{chunk_ref['class_counts'].tolist()}, decode class_counts "
          f"{dec_ref['class_counts'].tolist()}")


def check_compiled(servers: dict, params):
    """Both Pallas decode steps carry a Mosaic kernel; nothing served is
    interpreted."""
    import jax.numpy as jnp
    from repro.runtime import steps
    for name in ("pallas", "pallas_fused"):
        srv = servers[name]
        served = steps.mcma_serve_config(srv.cfg, backend=srv.backend)
        assert served.approx.interpret is False, (name, served.approx)
        hlo = srv.decode.lower(params, srv.cache,
                               jnp.zeros((BATCH, 1), jnp.int32),
                               jnp.ones((BATCH,), bool)).compile().as_text()
        assert "tpu_custom_call" in hlo, name
        print(f"[e] {name}: tpu_custom_call in the compiled decode step, "
              f"approx.interpret={served.approx.interpret}")


def describe(cfg):
    a = cfg.approx
    print(f"[config] {cfg.name} unreduced: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} query / {cfg.n_kv_heads} KV heads "
          f"(head_dim {cfg.hd}), d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.param_dtype}; ApproxFFN n_approx {a.n_approx}, d_hidden "
          f"{a.d_hidden}, exact_frac {a.exact_frac}, invoke_frac "
          f"{a.invoke_frac}; {cfg.params_count() / 1e9:.2f} G parameters "
          f"before the approximators")
    print(f"[config] serving: batch {BATCH}, max_len {MAX_LEN}, paged KV "
          f"(page {PAGE}), prefill_chunk {CHUNK}, route_scope tick, "
          f"use_mcma_dispatch")


def one_chip(seed: int, clock: CompileClock):
    import jax
    from repro.runtime.server import DecodeServer
    cfg = served_config()
    describe(cfg)
    c0 = clock.seconds
    params = init_params(cfg, seed)
    print(f"[b] weights initialised from seed {seed} under jit "
          f"({clock.seconds - c0:.1f} s compiling — set-up)")
    servers = {}
    for label, backend in (("b", "pallas"), ("c", "pallas_fused")):
        servers[backend] = DecodeServer(cfg, params,
                                        options=serve_options(backend))
        drain(servers[backend], cfg.vocab, seed, f"{label}:{backend}", clock)
        print(f"[{label}:{backend}] peak_bytes_in_use "
              f"{peak_bytes(jax.devices()[0])!r}")
    servers["xla"] = DecodeServer(cfg, params, options=serve_options("xla"))
    results = one_step_each(servers, {b: params for b in servers}, cfg, seed)
    compare({b: results[b] for b in BACKENDS}, "xla", "d")
    check_compiled(servers, params)
    print(f"[e] peak_bytes_in_use {peak_bytes(jax.devices()[0])!r}")


def four_chips(seed: int, clock: CompileClock):
    """The mesh server against the one-chip server on device 0.

    One device dispatches all rows against global capacity budgets; a mesh
    dispatches each data shard against its own (sharding/rules
    .shard_capacity), so where a budget binds the two legitimately drop
    different rows.  This phase therefore serves with budgets no shard can
    exceed (exact_frac = invoke_frac = 1.0): the routed and dispatched
    counts must then agree exactly, and what is left to differ is
    arithmetic."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.server import DecodeServer
    cfg = served_config()
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, exact_frac=1.0, invoke_frac=1.0))
    describe(cfg)
    mesh = make_host_mesh(data=2, model=2)
    print(f"[mesh] {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
          f"{[d.id for d in mesh.devices.flat]}")
    params = init_params(cfg, seed)
    servers = {
        "one_chip": DecodeServer(cfg, params,
                                 options=serve_options("pallas")),
        "mesh": DecodeServer(cfg, params,
                             options=serve_options("pallas", mesh=mesh))}
    mesh_devices = set(mesh.devices.flat)
    for what, tree in (("params", servers["mesh"].params),
                       ("cache", servers["mesh"].cache)):
        leaves = jax.tree_util.tree_leaves_with_path(tree)
        split = 0
        for path, leaf in leaves:
            devs = {s.device for s in leaf.addressable_shards}
            assert devs == mesh_devices, (what, path, devs)
            split += leaf.addressable_shards[0].data.shape != leaf.shape
        assert split, f"no {what} leaf is split across the mesh"
        print(f"[mesh] every {what} leaf has shards on all 4 devices; "
              f"{split} of {len(leaves)} leaves are split, the rest "
              f"replicated")
    results = one_step_each(
        servers, {"one_chip": params, "mesh": servers["mesh"].params},
        cfg, seed)
    compare(results, "one_chip", "mesh")
    for name, srv in servers.items():
        drain(srv, cfg.vocab, seed, f"mesh:{name}", clock)
    for d in jax.devices()[:4]:
        print(f"[mesh] device {d.id} peak_bytes_in_use {peak_bytes(d)!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"[a] platform {dev.platform}, device_kind {dev.device_kind!r}, "
          f"{len(devices)} device(s); compile cache {cache_dir}")
    clock = CompileClock()
    if args.chips == 4:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    print(f"[done] {clock.seconds:.1f} s of tracing and compiling over "
          f"{clock.compiles} programs (set-up time)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
