"""Serving launcher: batched decode with the continuous-batching server.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \\
        --smoke --requests 16 --max-new 24

Without ``--smoke`` the config is served at its published widths, e.g.
internlm2-1.8b on one TPU v5e chip:

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \\
        --mcma-dispatch --route-scope tick --kv-page-size 16 --batch 8 \\
        --max-len 1024

Mesh deployments (``--data``/``--model``) shard params/cache by the
declarative rules and serve every decode tick through the
shard_map-native MCMA dispatch when ``--mcma-dispatch`` is on (on 8 CPU
devices: XLA_FLAGS=--xla_force_host_platform_device_count=8 and
``--data 4 --model 2``).

Serving flags come from the shared ``runtime/cli.add_serve_options``
inventory (one surface with examples/serve_decode.py and
benchmarks/bench_serve.py) and fold into a ``ServeOptions`` via
``ServeOptions.from_args`` — only launcher-specific knobs (arch, mesh
shape, workload) are declared here.
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.runtime.cli import add_serve_options


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--approx", action="store_true")
    ap.add_argument("--data", type=int, default=0,
                    help="mesh data-axis size (0 = no mesh, single device)")
    ap.add_argument("--model", type=int, default=1,
                    help="mesh model-axis size (with --data)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    add_serve_options(ap, batch=4, max_len=128)
    args = ap.parse_args(argv)

    import functools

    import jax
    import numpy as np
    from repro.compile_cache import use_compile_cache
    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.runtime.options import ServeOptions
    from repro.runtime.server import DecodeServer, Request

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = None
    if args.data:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=args.data, model=args.model)
        assert args.batch % args.data == 0, \
            "--batch must divide by --data for the sharded dispatch path"
    options = ServeOptions.from_args(args, mesh=mesh)
    if args.approx or options.use_mcma_dispatch:
        cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, enable=True,
            library_size=options.library.library_size
            if options.library else cfg.approx.library_size))
    # one compiled init: a full-width model would otherwise initialise
    # op by op
    params = jax.jit(functools.partial(M.init_model, cfg=cfg))(
        jax.random.PRNGKey(args.seed))
    server = DecodeServer(cfg, params, options=options)

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len)
                    .astype(np.int32), max_new=args.max_new)
            for i in range(args.requests)]
    if options.qos_tiers:
        # mixed-tier request wave: cycle the tier table's bounds (plus a
        # default-tier request) so one batch carries every QoS level
        bounds = server.tier_bounds
        for i, r in enumerate(reqs):
            choices = list(bounds) + [None]
            r.error_bound = choices[i % len(choices)]
    for r in reqs:
        server.submit(r)
    stats = server.run_until_drained()
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens, "
          f"{stats['ticks']} ticks ({stats['prefill_ticks']} prefill, "
          f"chunk={server.prefill_chunk}), {stats['wall_s']:.1f}s "
          f"({toks / max(stats['wall_s'], 1e-9):.1f} tok/s aggregate)")
    ttft = [r.first_token_tick - r.arrival_tick for r in reqs
            if r.first_token_tick is not None]
    if ttft:
        print(f"ttft: mean {np.mean(ttft):.1f} ticks, "
              f"max {max(ttft)} ticks")
    if stats["undrained_queued"] or stats["undrained_inflight"]:
        print(f"WARNING: undrained — {stats['undrained_queued']} queued, "
              f"{stats['undrained_inflight']} in flight marked aborted")
    if mesh is not None:
        print(f"mesh: data={args.data} model={args.model} "
              f"({len(jax.devices())} devices, shard_map-native dispatch)")
    if "invocation_rate" in stats:
        print(f"mean invocation rate: {stats['invocation_rate']:.3f}")
    if "served_invocation_rate" in stats:
        print(f"served invocation rate: {stats['served_invocation_rate']:.3f}"
              f" (dropped {stats['dropped_rows']:.1f} rows,"
              f" frac {stats['dropped_frac']:.4f})")
    if "per_tier" in stats:
        for p in stats["per_tier"]:
            print(f"tier {p['tier']} (bound {p['error_bound']:.3f}, "
                  f"margin {p['margin']:+.2f}): {p['rows']:.0f} rows, "
                  f"served invocation {p['served_invocation_rate']:.3f}, "
                  f"dropped_frac {p['dropped_frac']:.4f}")
    if "residency" in stats:
        r = stats["residency"]
        print(f"residency: final hot set {r['final_residency']} after "
              f"{r['swap_count']} swaps "
              f"(off-set exact rows {stats['off_set_exact_rows']:.1f})")
    if "autotune" in stats:
        a = stats["autotune"]
        print(f"autotune: final point {a['final_point']} after "
              f"{len(a['switches'])} switches")
        if server.routed_history:
            lad = server.derived_ladder()
            print("ladder_from_counts (served class-count quantiles -> "
                  "asymmetric per-class rungs for the next deployment):")
            for r in lad:
                print(f"  exact_frac={r.exact_frac:.3f} "
                      f"invoke_fracs={tuple(round(f, 3) for f in r.invoke_fracs)}")
    assert done == len(reqs), "server failed to drain"
    return stats


if __name__ == "__main__":
    main()
