"""Runs one cell of the chip benchmark once, in this one process.

    python3 chipbench/run.py --workload internlm2-1.8b.chat --seed 7 \
        --seconds 40 --trace 0

The cell is looked up by name in ``BENCHMARK.json``: its configuration
file (``chipbench/configs/<config>.json``), the plain reference that file
names (``chipbench/references/<reference>.py``), its traffic mix
(``chipbench/traffic/<mix>.json``) and its metrics, each computed by the
reader ``chipbench/metrics/<metric>.py``.  Weights are made on the device
from ``--seed``; the traffic is generated from it.  Set-up (start of this
process to the first timed tick) covers JAX's start, the weights, loading
or compiling every program, two requests served to the end and
``warmup_s`` of the cell's own traffic.  Then the window runs for
``--seconds``.  With ``--trace 1`` the last seconds of the window are
traced and the per-layer metrics are reported instead of the end-to-end
ones.

After the window the program's state is freed and what it served is
compared with the plain reference (``chipbench/check.py``).  The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object.  Without a TPU, or with fewer chips than the cell asks
for, the run prints no result and exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import typing
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import traffic as traffic_lib  # noqa: E402

CONFIG_DIR = ROOT / "chipbench" / "configs"
METRIC_DIR = ROOT / "chipbench" / "metrics"
REFERENCE_DIR = ROOT / "chipbench" / "references"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 6.0

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's own tracing, lowering and compile durations and counts
    the programs compiled (a cache hit counts too: it still lowers)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == _COMPILE_EVENTS[-1]


def jax_with_cache():
    """JAX, with its persistent compilation cache in the checkout's
    ``.jax_cache`` whatever the environment says, keeping every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        return json.load(f)


def cell_of(bench: dict, workload: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == workload:
            return c
    raise SystemExit(f"run: no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: a metric without ``workloads`` belongs to every cell."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def _load(path: Path, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return _load(METRIC_DIR / f"{name}.py", f"chipbench_metric_{name}").read


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def reference_of(conf: dict):
    """The ``Reference`` class bound in ``chipbench/references/<name>.py``,
    ``<name>`` being the configuration's ``reference``.  A missing,
    malformed or unknown name stops the run here, before anything is
    served.  The contract a ``Reference`` keeps is in ``check.py``."""
    name = conf.get("reference")
    path = REFERENCE_DIR / f"{name}.py"
    if not isinstance(name, str) or not _NAME.fullmatch(name) \
            or not path.is_file():
        raise SystemExit(f"run: configuration {conf.get('name')!r} names "
                         f"reference {name!r}, which is no file <name>.py "
                         f"in {REFERENCE_DIR}")
    return _load(path, f"chipbench_reference_{name}").Reference


def model_key(seed: int):
    """The PRNG key of a seed; seeds past 32 bits fold their high word in
    (a plain key keeps only the low word)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def _frozen(cls, fields: dict):
    """``cls(**fields)`` with what JSON cannot say made so: a dict for a
    field whose type is a dataclass becomes that dataclass, and a list
    becomes a tuple, so that the result can be hashed."""
    types = typing.get_type_hints(cls)

    def frozen(k, v):
        if isinstance(v, dict) and dataclasses.is_dataclass(types.get(k)):
            return _frozen(types[k], v)
        return tuple(frozen(None, x) for x in v) if isinstance(v, list) else v
    return cls(**{k: frozen(k, v) for k, v in fields.items()})


def model_config(conf: dict):
    """The program's ``ModelConfig`` of a configuration file: its
    ``model`` section with ``approx`` beside it."""
    from repro.configs.base import ModelConfig
    return _frozen(ModelConfig, dict(conf["model"], approx=conf["approx"]))


def serve_options(conf: dict):
    from repro.runtime.options import ServeOptions
    s = conf["serve"]
    return ServeOptions(batch=s["batch"], max_len=s["max_len"],
                        use_mcma_dispatch=True, route_scope=s["route_scope"],
                        kv_page_size=s["kv_page_size"],
                        prefill_chunk=s["prefill_chunk"],
                        backend=s["backend"], greedy=True)


@dataclasses.dataclass
class Served:
    """What one run of the program leaves behind on the host."""

    setup_s: float
    window: object            # serve.WindowNumbers
    t0: float
    t1: float
    ticks: list               # serve.Tick, on the host, from the start
    first_tick: int           # index of the window's first tick
    last_tick: int            # one past the window's last tick
    trace_ticks: tuple        # (first, last) tick of the traced stretch
    counters: dict            # server counters differenced over the window
    stamps: list
    compiles_in_window: int
    memory_peak_bytes: int
    trace_dir: str | None


def _counters(server) -> dict:
    import numpy as np
    return {"dispatched": np.array(server.dispatched_sum, float),
            "routed": np.array(server.routed_sum, float),
            "dropped": float(server.dropped_sum),
            "prefill_tokens": int(server.prefill_tokens)}


def make_params(conf: dict, seed: int):
    """The model's weights from the seed, made on the device in one jitted
    call, in the type they are served in."""
    import jax
    from repro.models import model as M
    cfg = model_config(conf)
    return jax.block_until_ready(jax.jit(
        lambda k: M.init_model(k, cfg))(model_key(seed)))


def serve_cell(conf: dict, spec: dict, *, seed: int, seconds: float,
               trace: bool, t_start: float, clock: CompileClock,
               params=None) -> Served:
    """Set up the program, serve the warm-up and the window, and return
    only host data, so that the program's device state is freed when
    this returns (``params``, when given, are the caller's)."""
    import jax
    from repro.runtime.server import DecodeServer
    from chipbench.serve import (Client, Recorder, compile_warmup,
                                 window_numbers)
    cfg = model_config(conf)
    if params is None:
        params = make_params(conf, seed)
    server = DecodeServer(cfg, params, options=serve_options(conf))
    recorder = Recorder(server)
    compile_warmup(server, cfg.vocab, conf["serve"]["prefill_chunk"], seed)
    streams = [traffic_lib.Traffic(spec, seed, vocab=cfg.vocab,
                                   max_len=conf["serve"]["max_len"],
                                   stream=k) for k in (1, 2)]
    client = Client(server, streams[0])
    client.run(time.perf_counter() + float(spec["warmup_s"]))
    client.switch(streams[1])
    t0 = time.perf_counter()
    before, n0, c0 = _counters(server), len(recorder.ticks), clock.compiles
    trace_dir, trace_ticks = None, (n0, n0)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        client.run(t0 + max(0.0, seconds - TRACE_SECONDS))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        k0 = len(recorder.ticks)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t1 = client.run(t0 + seconds)
        k1 = len(recorder.ticks)
        jax.profiler.stop_trace()
        trace_ticks = (k0, k1)
    else:
        t1 = client.run(t0 + seconds)
    after, n1 = _counters(server), len(recorder.ticks)
    compiles = clock.compiles - c0
    counters = {k: after[k] - before[k] for k in after}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    win = window_numbers(client.stamps, t0, t1)
    ticks = recorder.to_host()
    return Served(t0 - t_start, win, t0, t1, ticks, n0, n1, trace_ticks,
                  counters, client.stamps, compiles, peak, trace_dir)


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    conf: dict
    spec: dict
    served: Served
    window_ticks: list
    trace: object             # trace.TraceSummary, or None
    peaks: dict


def measure(conf: dict, spec: dict, metric_names: list[dict], *, seed: int,
            seconds: float, trace: bool, t_start: float, peaks: dict,
            clock: CompileClock) -> dict:
    """Everything of a run but the look for a chip: serve, read the
    metrics, check the outputs.  Returns the result object."""
    from chipbench import check, trace as trace_lib
    reference_of(conf)           # a wrong name stops the run before serving
    served = serve_cell(conf, spec, seed=seed, seconds=seconds, trace=trace,
                        t_start=t_start, clock=clock)
    gc.collect()
    summary = None
    if served.trace_dir is not None:
        summary = trace_lib.TraceSummary(trace_lib.read_events(
            trace_lib.find_xplane(served.trace_dir)))
    ctx = Context(conf, spec, served,
                  served.ticks[served.first_tick:served.last_tick],
                  summary, peaks)
    metrics = {}
    for m in metric_names:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    win = served.window
    report(served, conf)
    t = time.perf_counter()
    verdict = check.check_served(conf, spec, seed, served)
    print(f"[check] {time.perf_counter() - t!r} s after the window")
    device = {"memory_peak_bytes": served.memory_peak_bytes}
    result = {"correct": verdict["correct"], "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
        shutil.rmtree(served.trace_dir, ignore_errors=True)
    result["check"] = verdict["numbers"]
    return result


def report(served: Served, conf: dict):
    """Earlier lines: counts and the spread of what was measured."""
    import numpy as np
    win = served.window
    q = lambda xs, p: float(np.percentile(xs, p)) if len(xs) else None
    print(f"[window] {win.seconds!r} s; attempted {win.attempted}, failed "
          f"{win.failed}; {win.generated} tokens generated, "
          f"{served.counters['prefill_tokens']} prompt tokens chunked; "
          f"{served.last_tick - served.first_tick} ticks")
    print(f"[window] ttft median {q(win.ttft_s, 50)!r} s p95 "
          f"{q(win.ttft_s, 95)!r} s over {len(win.ttft_s)} requests; "
          f"itl median {q(win.itl_s, 50)!r} s p95 {q(win.itl_s, 95)!r} s "
          f"over {len(win.itl_s)} gaps")
    if win.lateness_s:
        print(f"[window] generator lateness median "
              f"{q(win.lateness_s, 50)!r} s p95 {q(win.lateness_s, 95)!r} s "
              f"max {max(win.lateness_s)!r} s")
    held = [t.pages for t in served.ticks[served.first_tick:
                                          served.last_tick]]
    if held:
        from chipbench.readers import pool_pages
        print(f"[window] KV pages held: mean {float(np.mean(held))!r}, max "
              f"{max(held)} of {pool_pages(conf)} allocated")
    print(f"[window] programs compiled inside the window: "
          f"{served.compiles_in_window}")
    print(f"[setup] {served.setup_s!r} s; memory_peak_bytes "
          f"{served.memory_peak_bytes}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    conf = load_config(cell["config"])
    spec = traffic_lib.load(cell["traffic"])
    jax = jax_with_cache()
    from chipbench.trace import UnknownDevice, peaks_for
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"run: needs a TPU, but JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run: {args.workload} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks = peaks_for(dev.device_kind)
    except UnknownDevice as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    print(f"[device] platform {dev.platform}, device_kind "
          f"{dev.device_kind!r}, {len(devices)} device(s); compile cache "
          f"{CACHE_DIR}")
    clock = CompileClock()
    result = measure(conf, spec,
                     metrics_for(bench, args.workload, bool(args.trace)),
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START, peaks=peaks,
                     clock=clock)
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), **result["device"]}
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
