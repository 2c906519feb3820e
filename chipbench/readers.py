"""The arithmetic of the metric readers.  Each file in
``chipbench/metrics/`` names one metric and binds its ``read`` to one of
these; a reader returns None where its cell gives it nothing to read.

``ctx`` is ``run.Context``: the configuration, the mix, what was served
(host stamps, the recorded ticks, the server's counters differenced over
the window) and, in a traced run, the trace's summary.
"""
from __future__ import annotations

import numpy as np

DECODE_PROGRAM = "jit_decode_step"
CHUNK_PROGRAM = "jit_prefill_chunk_step"


def setup_s(ctx):
    return ctx.served.setup_s


def _p95_ms(xs):
    return float(np.percentile(xs, 95)) * 1e3 if len(xs) else None


def ttft_p95_ms(ctx):
    return _p95_ms(ctx.served.window.ttft_s)


def itl_p95_ms(ctx):
    return _p95_ms(ctx.served.window.itl_s)


def dropped_frac(ctx):
    c = ctx.served.counters
    routed = float(c["routed"].sum())
    return c["dropped"] / routed if routed else None


def pool_pages(conf: dict) -> int:
    s = conf["serve"]
    return s.get("kv_pages") or s["batch"] * s["max_len"] // s["kv_page_size"]


def kv_pages_held(ctx):
    """The KV pool's pages held by requests, as a share of the pool,
    averaged over the window's step calls, in per cent."""
    held = [t.pages for t in ctx.window_ticks]
    return 100.0 * float(np.mean(held)) / pool_pages(ctx.conf) \
        if held else None


def served_invocation_rate(ctx):
    c = ctx.served.counters
    routed = float(c["routed"].sum())
    return float(c["dispatched"][1:].sum()) / routed if routed else None


def device_idle_frac(ctx):
    return ctx.trace.idle_frac() if ctx.trace is not None else None


def _step_ms(ctx, program):
    if ctx.trace is None:
        return None
    calls = ctx.trace.module_calls(program)
    return 1e3 * sum(c.dur for c in calls) / len(calls) if calls else None


def decode_step_ms(ctx):
    return _step_ms(ctx, DECODE_PROGRAM)


def prefill_step_ms(ctx):
    return _step_ms(ctx, CHUNK_PROGRAM)
