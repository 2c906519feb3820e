"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \\
        --steps 50 --ckpt-dir runs/ck_olmo

On a real cluster this binary runs once per host (jax.distributed
initializes from the cluster env); here it drives the same Trainer on
whatever devices exist.  ``--smoke`` selects the reduced config;
``--mesh data,model`` shards over local devices.
"""
from __future__ import annotations

import argparse
import dataclasses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--approx", action="store_true",
                    help="enable the MCMA ApproxFFN layer")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="", help="e.g. '4,2' => (data, model)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.configs.registry import get_config, smoke_config
    from repro.data.pipeline import SyntheticLM
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.approx:
        cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, enable=True))
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(shape, ("data", "model")[:len(shape)])

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq_len,
                     global_batch=args.batch, seed=args.seed)
    tc = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, base_lr=args.lr,
                       warmup=max(args.steps // 10, 1),
                       grad_accum=args.grad_accum)
    out = Trainer(cfg, tc, ds, mesh=mesh, seed=args.seed).run()
    print(f"done: {out}")
    return out


if __name__ == "__main__":
    main()
