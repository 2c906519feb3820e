"""Readings for the correctness limits of one cell, on the chip: the
program's ``max_logit_gap`` over many seeds, and on some of them the
control (the reference with every weight matmul in float8 e4m3, put in
the program's place on the same served sample) judged by the same
verdict, with int8 beside it.

    python3 chipbench/control.py --workload internlm2-1.8b.chat \
        --seeds 12 --control-seeds 3 --seconds 20 --first-seed 1000

Everything runs in this one process: each seed serves its own short
window at the cell's load, then is checked.  One JSON line per seed, then
a summary line: the largest program gap (the lower reading), the
smallest control gap (the upper reading), and whether every program run
came out correct and every fp8 control run not correct.  The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, run, traffic  # noqa: E402


def readings(conf: dict, spec: dict, seeds, control_seeds: set,
             seconds: float, clock) -> list[dict]:
    out = []
    for seed in seeds:
        t = time.perf_counter()
        served = run.serve_cell(conf, spec, seed=seed, seconds=seconds,
                                trace=False, t_start=t, clock=clock)
        gc.collect()                      # the server's state, before the reference
        v = check.check_served(
            conf, spec, seed, served,
            controls=("fp8", "int8") if seed in control_seeds else ())
        row = {"seed": seed, "correct": v["correct"],
               **{k: x["value"] for k, x in v["numbers"].items()}}
        for kind, c in v["controls"].items():
            row[f"control_{kind}_correct"] = c["correct"]
            row[f"control_{kind}_max_logit_gap"] = \
                c["numbers"]["max_logit_gap"]["value"]
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    cell = run.cell_of(bench, args.workload)
    conf = run.load_config(cell["config"])
    spec = traffic.load(cell["traffic"])
    jax = run.jax_with_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = readings(conf, spec, seeds, set(seeds[:args.control_seeds]),
                    args.seconds, run.CompileClock())
    summary = {"workload": args.workload,
               "program_max_logit_gap": max(r["max_logit_gap"]
                                            for r in rows),
               "all_correct": all(r["correct"] for r in rows),
               "fp8_control_never_correct": not any(
                   r.get("control_fp8_correct", False) for r in rows)}
    for k in ("control_int8_max_logit_gap", "control_fp8_max_logit_gap"):
        ctl = [r[k] for r in rows if k in r]
        summary[k.replace("max", "min_of_max")] = min(ctl) if ctl else None
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
