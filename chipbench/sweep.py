"""Finds the highest arrival rate a cell's open-loop mix sustains on the
chip, by serving it at each of a list of rates in turn.

    python3 chipbench/sweep.py --workload internlm2-1.8b.chat \
        --rates 1.5,2,2.5,3 --seconds 40

One process, one set of weights; each rate gets a fresh server, the
mix's warm-up and a window of ``--seconds``.  A rate is sustained when the
requests waiting at the end of its window are no more than at its start
plus a batch, and the first-token times of the window's second half are
no longer than its first half's by more than half.  One JSON line per
rate.  The cell's traffic file then takes 0.8 x the highest sustained
rate, written in as a number.
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

import numpy as np  # noqa: E402

from chipbench import run, traffic  # noqa: E402


def at_rate(conf, spec, rate, seed, seconds, params, clock) -> dict:
    spec = dict(spec, rate_per_s=rate)
    served = run.serve_cell(conf, spec, seed=seed, seconds=seconds,
                            trace=False, t_start=time.perf_counter(),
                            clock=clock, params=params)
    win = served.window
    t0, t1 = served.t0, served.t1
    waiting = lambda t: sum(1 for st in served.stamps if st.due <= t
                            and not (st.times and st.times[0] <= t))
    due = sorted((st.due, (st.times[0] if st.times and st.times[0] <= t1
                           else t1) - st.due)
                 for st in served.stamps if t0 <= st.due < t1)
    half = len(due) // 2
    first, second = [x for _, x in due[:half]], [x for _, x in due[half:]]
    batch = conf["serve"]["batch"]
    row = {"rate_per_s": rate, "due": len(due),
           "finished": sum(1 for st in served.stamps if st.finished
                           and t0 <= st.due < t1),
           "waiting_start": waiting(t0), "waiting_end": waiting(t1),
           "ttft_p50_s": float(np.median([x for _, x in due])),
           "ttft_p90_s": float(np.percentile([x for _, x in due], 90)),
           "ttft_p95_s": float(np.percentile([x for _, x in due], 95)),
           "itl_p95_s": float(np.percentile(win.itl_s, 95)),
           "ttft_mean_first_half_s": float(np.mean(first)),
           "ttft_mean_second_half_s": float(np.mean(second))}
    row["sustained"] = (row["waiting_end"] <= row["waiting_start"] + batch
                        and row["ttft_mean_second_half_s"]
                        <= 1.5 * row["ttft_mean_first_half_s"] + 0.1)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 4242)
    args = ap.parse_args(argv)
    cell = run.cell_of(run.load_benchmark(), args.workload)
    conf = run.load_config(cell["config"])
    spec = traffic.load(cell["traffic"])
    jax = run.jax_with_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    params = run.make_params(conf, args.seed)
    clock = run.CompileClock()
    for rate in (float(r) for r in args.rates.split(",")):
        print(json.dumps(at_rate(conf, spec, rate, args.seed, args.seconds,
                                 params, clock)), flush=True)
        gc.collect()                      # one rate's server, before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
