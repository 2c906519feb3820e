"""Pallas kernel micro-bench (interpret mode on CPU: correctness + VMEM
working-set accounting, NOT wall-time — the target is TPU v5e).

For each kernel configuration we report the analytic per-tile VMEM bytes
(must be << 16 MiB more) and the HBM-traffic saving vs the unfused XLA
path that materializes the hidden activations.

The fused-dispatch leg sweeps block_t over the gather/scatter-fused
weight-switch kernel (kernels/fused_dispatch.py) vs the unfused
class-sort path: interpret-mode wall time (XLA-level op mix, not TPU
kernel speed), the fused kernel's VMEM residency bound (x and the
(T+1)-row output stay resident across the whole grid — the bound that
decides when fusion is sound), and a BITWISE equality gate per block
size.  Writes benchmarks/out/kernels.csv.
"""
from __future__ import annotations

import csv
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

OUT = os.path.join(os.path.dirname(__file__), "out")
VMEM = 16 * 2 ** 20
LANE = 128


def mlp_vmem(block_t, d_in, d_h, d_out, itemsize=2):
    tile = (block_t * d_in + d_in * d_h + block_t * d_h
            + d_h * d_out + block_t * d_out)
    return tile * itemsize


def fused_vmem(t, d_in, d_h, d_out, block_t, itemsize=2):
    """Fused dispatch kernel residency: the whole (T, d_in) activation
    block and the (T+1, d_out_p) output block live in VMEM for the full
    grid (that's what makes the in-kernel gather/scatter free of HBM
    traffic), plus one weight tile and the compiled branch's f32 staging
    scratch: the activation and output blocks again, and one tile of
    each.  This is the bound that decides when fusion is sound — past
    it, fall back to the unfused class-sort path."""
    pad = lambda v: ((v + LANE - 1) // LANE) * LANE
    pad8 = lambda v: ((v + 7) // 8) * 8
    d_in_p, d_h_p, d_out_p = pad(d_in), pad(d_h), pad(d_out)
    resident = t * d_in + (t + 1) * d_out_p
    tile = d_in_p * d_h_p + d_h_p + d_h_p * d_out_p + d_out_p
    scratch = (pad8(t) * d_in_p + block_t * (d_in_p + d_out_p)
               + pad8(t + 1) * d_out_p)
    return (resident + tile) * itemsize + scratch * 4


def hbm_saving(t, d_h, itemsize=2):
    """Unfused XLA writes+reads the (T, d_h) hidden activations."""
    return 2 * t * d_h * itemsize


def _fused_leg(rows):
    """block_t sweep: ops.switched_apply (class-sort + standalone
    gather/scatter) vs ops.switched_apply_fused (gather/scatter folded
    into the kernel), bitwise-gated at every block size."""
    t, n, d, d_h = 1024, 4, 256, 64
    key = jax.random.PRNGKey(7)
    x = (jax.random.normal(key, (t, d)) * 0.3).astype(jnp.bfloat16)
    ks = jax.random.split(key, 3)
    w1 = (jax.random.normal(ks[0], (n, d, d_h)) * 0.1).astype(jnp.bfloat16)
    b1 = jnp.zeros((n, d_h), jnp.bfloat16)
    w2 = (jax.random.normal(ks[1], (n, d_h, d)) * 0.1).astype(jnp.bfloat16)
    b2 = jnp.zeros((n, d), jnp.bfloat16)
    cls = jax.random.randint(ks[2], (t,), 0, n)
    want = ref.switched_mlp_ref(x, cls, w1, b1, w2, b2)
    for bt in (64, 128, 256):
        times = {}
        outs = {}
        for label, fn in (("unfused", ops.switched_apply),
                          ("fused", ops.switched_apply_fused)):
            y = fn(x, cls, w1, b1, w2, b2, block_t=bt, interpret=True)
            jax.block_until_ready(y)             # compile off the clock
            t0 = time.perf_counter()
            for _ in range(3):
                y = fn(x, cls, w1, b1, w2, b2, block_t=bt, interpret=True)
            jax.block_until_ready(y)
            times[label] = (time.perf_counter() - t0) / 3 * 1e3
            outs[label] = np.asarray(y)
        assert np.array_equal(outs["fused"], outs["unfused"]), \
            f"fused != unfused bitwise at block_t={bt}"
        err = float(jnp.max(jnp.abs(outs["fused"].astype(np.float32)
                                    - np.asarray(want, np.float32))))
        vm = fused_vmem(t, d, d_h, d, bt)
        rows.append({"kernel": f"fused_dispatch_bt{bt}", "T": t,
                     "n_approx": n, "block_t": bt,
                     "vmem_tile_bytes": vm, "vmem_ok": vm < VMEM,
                     "hbm_saving_bytes": hbm_saving(t, d_h),
                     "max_abs_err_vs_ref": round(err, 5),
                     "ms_fused_interp": round(times["fused"], 3),
                     "ms_unfused_interp": round(times["unfused"], 3)})
        print(f"fused_dispatch_bt{bt:<4d} vmem-resident={vm/2**20:.2f}MiB "
              f"interp {times['fused']:.1f} vs {times['unfused']:.1f} ms "
              f"(unfused) err={err:.4f}", flush=True)


def main():
    os.makedirs(OUT, exist_ok=True)
    rows = []
    cases = [
        # (name, T, n, d_in, d_h, d_out, block_t)
        ("approx_paper_bs", 4096, 1, 6, 8, 1, 256),
        ("approx_ffn_1b", 2048, 1, 2048, 256, 2048, 256),
        ("approx_ffn_3b", 2048, 1, 2560, 256, 2560, 512),
        ("switched_n3_1b", 2048, 3, 2048, 256, 2048, 256),
        ("switched_n8_1b", 2048, 8, 2048, 256, 2048, 256),
    ]
    for name, t, n, d_in, d_h, d_out, bt in cases:
        key = jax.random.PRNGKey(0)
        x = (jax.random.normal(key, (t, d_in)) * 0.3).astype(jnp.bfloat16)
        ks = jax.random.split(key, 5)
        if n == 1:
            w1 = (jax.random.normal(ks[0], (d_in, d_h)) * 0.1).astype(jnp.bfloat16)
            b1 = jnp.zeros((d_h,), jnp.bfloat16)
            w2 = (jax.random.normal(ks[1], (d_h, d_out)) * 0.1).astype(jnp.bfloat16)
            b2 = jnp.zeros((d_out,), jnp.bfloat16)
            got = ops.mlp_apply(x, w1, b1, w2, b2, block_t=bt, interpret=True)
            want = ref.mlp_forward_ref(x, w1, b1, w2, b2)
        else:
            w1 = (jax.random.normal(ks[0], (n, d_in, d_h)) * 0.1).astype(jnp.bfloat16)
            b1 = jnp.zeros((n, d_h), jnp.bfloat16)
            w2 = (jax.random.normal(ks[1], (n, d_h, d_out)) * 0.1).astype(jnp.bfloat16)
            b2 = jnp.zeros((n, d_out), jnp.bfloat16)
            cls = jax.random.randint(ks[2], (t,), 0, n)
            got = ops.switched_apply(x, cls, w1, b1, w2, b2, block_t=bt,
                                     interpret=True)
            want = ref.switched_mlp_ref(x, cls, w1, b1, w2, b2)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        vm = mlp_vmem(bt, d_in, d_h, d_out) * (1 if n == 1 else 1)  # per tile
        rows.append({"kernel": name, "T": t, "n_approx": n,
                     "block_t": bt, "vmem_tile_bytes": vm,
                     "vmem_ok": vm < VMEM,
                     "hbm_saving_bytes": hbm_saving(t, d_h),
                     "max_abs_err_vs_ref": round(err, 5)})
        print(f"{name:18s} vmem/tile={vm/2**20:.2f}MiB "
              f"hbm_saved={hbm_saving(t, d_h)/2**20:.1f}MiB err={err:.4f}",
              flush=True)
    _fused_leg(rows)
    fields = list(rows[0].keys())
    for r in rows:
        fields += [k for k in r if k not in fields]
    with open(os.path.join(OUT, "kernels.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fields, restval="")
        w.writeheader()
        w.writerows(rows)
    return rows


if __name__ == "__main__":
    main()
