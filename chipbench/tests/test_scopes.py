"""The reduction by the program's own names (chipbench/scopes.py): scope
attribution on hand-built events, the host spans a served tick records
here on the CPU, and the readers in a traced run of the harness."""
import dataclasses
import inspect
import time

import jax
import numpy as np
import pytest

from chipbench import run, scopes as S, trace as T
from chipbench.tests.helpers import PEAKS, tiny_conf, tiny_spec
from repro import scopes as names
from repro.configs.registry import get_config, smoke_config
from repro.models import model as M
from repro.runtime.options import ServeOptions
from repro.runtime.server import DecodeServer, Request

CHIP = "/device:TPU:0"
NEW_METRICS = ("decode_kv_ms.steady", "decode_attention_ms.steady",
               "decode_mcma_plan_ms.steady", "decode_switch_kernel_ms.steady",
               "decode_exact_ffn_ms.steady", "decode_unembed_ms.steady",
               "tick_host_idle_ms.steady", "queue_wait_ms.steady",
               "prefill_wait_ms.steady")


def _op(path, start, end, name="%fusion.1 = f32[8] fusion(...)"):
    return S.Op(CHIP, name, path, start, end)


def _run(program, start, end):
    return S.Op(CHIP, f"{program}(123)", "", start, end)


BODY = "jit(decode_step)/while/body/closed_call/"


def _hand_built():
    """One window [0, 10) holding two decode runs, [1, 2) and [4, 5),
    a chunk run [6, 7), and a decode run that leaves the window."""
    ops = []
    for base in (1.0, 4.0):
        ops += [
            _op(BODY + "attention/kv_gather/jit(_take)/select_n:",
                base, base + 0.30),
            _op(BODY + "attention/kv_write/scatter:", base + 0.30,
                base + 0.40),
            _op(BODY + "attention/dot_general:", base + 0.40, base + 0.45),
            _op("jit(decode_step)/mcma_plan/sort:", base + 0.45,
                base + 0.50),
            _op(BODY + "jit(switched_mlp)/mcma_switched_mlp/pallas_call:",
                base + 0.50, base + 0.55),
            _op(BODY + "exact_ffn/dot_general:", base + 0.55, base + 0.70),
            _op("jit(decode_step)/unembed/dot_general:", base + 0.70,
                base + 0.80),
            _op(BODY + "add:", base + 0.80, base + 0.85),
            # a loop holds its body's operations: left out
            _op(BODY, base, base + 0.9, name="%while.3 = (s32[]) while(...)"),
        ]
    ops += [_op(BODY + "attention/dot_general:", 6.0, 6.8),
            _op(BODY + "add:", 6.8, 6.9),
            # a run partly outside the window, and its operations
            _op(BODY + "attention/dot_general:", 9.5, 10.5)]
    modules = [_run(S.DECODE_PROGRAM, 1.0, 2.0),
               _run(S.DECODE_PROGRAM, 4.0, 5.0),
               _run(S.CHUNK_PROGRAM, 6.0, 7.0),
               _run(S.DECODE_PROGRAM, 9.5, 10.5)]
    spans = [S.Span(T.WINDOW_SPAN, 0.0, 10.0)]
    for t0 in (0.5, 3.5):         # two ticks: admit, prepare, step, read
        spans += [S.Span(names.TICK, t0, t0 + 2.0),
                  S.Span(names.ADMIT, t0, t0 + 0.2),
                  S.Span(names.RESET_SLOT, t0 + 0.1, t0 + 0.2),
                  S.Span(names.PREPARE, t0 + 0.2, t0 + 0.5),
                  S.Span(names.STEP, t0 + 0.5, t0 + 0.6),
                  S.Span(names.READ_STATS, t0 + 0.6, t0 + 1.8)]
    return ops, modules, spans


def test_innermost_scope_attribution():
    want = {BODY + "attention/kv_gather/gather:": names.KV_GATHER,
            BODY + "attention/kv_write/scatter:": names.KV_WRITE,
            BODY + "kv_write/dynamic_update_slice:": names.KV_WRITE,
            BODY + "attention/jit(_take)/dot_general:": names.ATTENTION,
            "jit(decode_step)/mcma_plan/jit(argsort)/sort:": names.MCMA_PLAN,
            BODY + "exact_ffn/dot_general:": names.EXACT_FFN,
            "jit(decode_step)/unembed/dot_general:": names.UNEMBED,
            BODY + "jit(switched_apply)/jit(switched_mlp)/"
            "mcma_switched_mlp/pallas_call:": names.SWITCH_KERNEL,
            BODY + "jit(switched_apply_fused)/mcma_switched_mlp_fused/"
            "pallas_call:": names.SWITCH_KERNEL,
            BODY + "add:": S.REMAINDER,
            "": S.REMAINDER}
    for path, cat in want.items():
        assert S.category(_op(path, 0, 1)) == cat, path


def test_step_split_skips_loops_and_clips_to_the_window():
    s = S.ScopeSummary(*_hand_built())
    split, n = s.step_split(S.DECODE_PROGRAM)
    assert n == 2                       # the run leaving the window is out
    want = {names.KV_GATHER: 0.30, names.KV_WRITE: 0.10,
            names.ATTENTION: 0.05, names.MCMA_PLAN: 0.05,
            names.SWITCH_KERNEL: 0.05,
            names.EXACT_FFN: 0.15, names.UNEMBED: 0.10, S.REMAINDER: 0.05}
    assert split == pytest.approx(want)
    # the while loop's 0.9 s is not counted again: the split sums to the
    # operations' own time
    assert sum(split.values()) == pytest.approx(0.85)
    chunk, n = s.step_split(S.CHUNK_PROGRAM)
    assert n == 1 and chunk == pytest.approx({names.ATTENTION: 0.8,
                                              S.REMAINDER: 0.1})


def test_idle_by_innermost_span_and_per_tick():
    s = S.ScopeSummary(*_hand_built())
    split, idle, in_runs = s.idle_split()
    # busy: [1, 1.85), [4, 4.85), [6, 6.9), [9.5, 10); the rest is idle
    assert idle == pytest.approx(10.0 - 2 * 0.85 - 0.9 - 0.5)
    assert sum(split.values()) == pytest.approx(idle)
    # tick 1 [0.5, 2.5): admit [0.5, 0.7) holds reset_slot [0.6, 0.7);
    # prepare [0.7, 1.0) is idle; step [1.0, 1.1) is busy; read_stats
    # [1.1, 2.3) is busy to 1.85; the tick alone is idle from 2.3 to 2.5
    assert split[names.ADMIT] == pytest.approx(2 * 0.1)
    assert split[names.RESET_SLOT] == pytest.approx(2 * 0.1)
    assert split[names.PREPARE] == pytest.approx(2 * 0.3)
    assert names.STEP not in split
    assert split[names.READ_STATS] == pytest.approx(2 * 0.45)
    assert split[names.TICK] == pytest.approx(2 * 0.2)
    assert split[S.OUTSIDE] == pytest.approx(idle - 2 * 1.15)
    # the ends of the decode runs (0.15 s) and of the chunk run (0.1 s)
    # fall between the operations of a running program
    assert in_runs == pytest.approx(2 * 0.15 + 0.1)
    assert s.tick_idle() == pytest.approx([1.15, 1.15])


def test_attribute_idle_matches_a_grid():
    rng = np.random.default_rng(3)
    spans, t = [], 0.0
    for _ in range(6):                  # ticks with nested children
        a = t + rng.uniform(0, 0.2)
        b = a + rng.uniform(0.5, 1.0)
        spans.append(S.Span("tick", a, b))
        c = a + rng.uniform(0, 0.2)
        d = c + rng.uniform(0.05, b - c)
        spans.append(S.Span("child", c, d))
        spans.append(S.Span("grandchild", c + (d - c) / 3, d - (d - c) / 3))
        t = b
    s0 = rng.uniform(0, t, 30)
    gaps = T.merge(zip(s0, s0 + rng.uniform(0, 0.3, 30)), 0.0, t)
    got = S.attribute_idle(gaps, spans)
    step = 1e-4
    grid = np.arange(0, t, step) + step / 2
    want: dict = {}
    for x in grid:
        if not any(a <= x < b for a, b in gaps):
            continue
        cover = [sp for sp in spans if sp.start <= x < sp.end]
        key = min(cover, key=lambda sp: sp.dur).name if cover else S.OUTSIDE
        want[key] = want.get(key, 0.0) + step
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=5e-3), k


def _tiny_server():
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, interpret=True))
    return DecodeServer(cfg, M.init_model(jax.random.PRNGKey(0), cfg),
                        options=ServeOptions(
                            batch=2, max_len=64, use_mcma_dispatch=True,
                            route_scope="tick", kv_page_size=8,
                            prefill_chunk=8))


def _recorded_server_trace(tmp_path):
    srv = _tiny_server()
    rng = np.random.default_rng(0)
    for i, n in enumerate((20, 3, 6)):
        srv.submit(Request(rid=i, prompt=rng.integers(0, 512, n,
                                                      dtype=np.int32),
                           max_new=3))
    srv.tick()                          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        while srv.queue or any(s is not None for s in srv.slots):
            srv.tick()
    jax.profiler.stop_trace()
    return S.read_scoped(T.find_xplane(tmp_path),
                         set(names.TICK_SPANS) | {T.WINDOW_SPAN})


def test_a_served_tick_records_its_spans_nested(tmp_path):
    _, _, spans = _recorded_server_trace(tmp_path)
    assert {s.name for s in spans} == set(names.TICK_SPANS) | {T.WINDOW_SPAN}
    ticks = sorted((s for s in spans if s.name == names.TICK),
                   key=lambda s: s.start)
    children = [s for s in spans if s.name not in (names.TICK,
                                                   T.WINDOW_SPAN)]
    order = [names.ADMIT, names.ABORT_CHECK, names.PREPARE, names.STEP,
             names.READ_STATS, names.SAMPLE, names.COMMIT]
    phases = set()
    for tick in ticks:
        inside = sorted((c for c in children if tick.start <= c.start
                         and c.end <= tick.end), key=lambda c: c.start)
        top = [c for c in inside if c.name != names.RESET_SLOT]
        # the tick's phases follow one another, in order, without overlap
        assert [c.name for c in top] == [n for n in order
                                         if n in {c.name for c in top}]
        assert all(a.end <= b.start for a, b in zip(top, top[1:]))
        phases.add(tuple(c.name for c in top))
        for r in (c for c in inside if c.name == names.RESET_SLOT):
            assert any(a.start <= r.start and r.end <= a.end
                       for a in top if a.name == names.ADMIT)
    # every child lies in a tick
    assert all(any(t.start <= c.start and c.end <= t.end for t in ticks)
               for c in children)
    # a chunk tick samples nothing; a decode tick does
    assert (names.ADMIT, names.ABORT_CHECK, names.PREPARE, names.STEP,
            names.READ_STATS, names.COMMIT) in phases
    assert tuple(order) in phases


def test_names_are_the_programs():
    assert S.names is names
    src = inspect.getsource(S)
    for value in names.DEVICE_SCOPES + names.SWITCH_KERNELS \
            + names.TICK_SPANS:
        assert f'"{value}"' not in src, value
    fields = {f.name for f in dataclasses.fields(Request)}
    assert {"arrival_s", "admitted_s", "first_token_s"} <= fields
    bench = run.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for m in NEW_METRICS:
        assert callable(run.reader(m))
        assert per_layer[m]["workloads"] == ["internlm2-1.8b.chat"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the harness on a tiny cell, on this CPU, keeping
    its context for the readers."""
    kept = {}
    real = run.Context

    def keep(*a, **k):
        kept["ctx"] = real(*a, **k)
        return kept["ctx"]

    bench = run.load_benchmark()
    metrics = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "Context", keep)
    mp.setattr(run.shutil, "rmtree", lambda *a, **k: None)
    try:
        res = run.measure(tiny_conf("internlm2-1.8b"), tiny_spec(), metrics,
                          seed=2 ** 31 + 5, seconds=8.0, trace=True,
                          t_start=time.perf_counter(), peaks=PEAKS,
                          clock=run.CompileClock())
    finally:
        mp.undo()
    return res, kept["ctx"]


def test_readers_in_a_traced_run(traced, capsys):
    res, ctx = traced
    got = res["metrics"]
    # no chip plane here: the step splits have nothing to read
    assert not any(m.startswith("decode_") for m in got)
    assert got["tick_host_idle_ms.steady"]["value"] > 0
    assert got["queue_wait_ms.steady"]["value"] >= 0
    assert got["prefill_wait_ms.steady"]["value"] > 0
    # the same ticks the harness recorded: one serve.tick span each
    s = S.summary(ctx)
    assert len(s.ticks()) >= ctx.served.trace_ticks[1] \
        - ctx.served.trace_ticks[0] - 1
    # the stamps: each wait is a difference of two perf_counter stamps
    waits = [st.req.admitted_s - st.req.arrival_s for st in ctx.served.stamps
             if st.req.admitted_s is not None
             and ctx.served.t0 <= st.req.admitted_s < ctx.served.t1]
    assert got["queue_wait_ms.steady"]["value"] == pytest.approx(
        1e3 * np.mean(waits))


def test_readers_without_program_names_read_nothing(traced, monkeypatch):
    _, ctx = traced
    monkeypatch.setattr(S, "names", None)
    for m in NEW_METRICS[:7]:
        assert run.reader(m)(ctx) is None, m
