"""The arithmetic of the Mamba2 mixer's readers: its device time per
step run, split by the program's scopes, and the share of the HBM
roofline that the decode step's state update reaches.

The scopes are the program's (``repro.scopes.SSM_MIXER`` and
``SSM_STATE``, the second nested in the first).  A program without
those names, or a cell without Mamba2 layers, gives these readers
nothing to read: they return None and do not raise.
"""
from __future__ import annotations

import bisect

from chipbench import scopes as S

F32_BYTES = 4


def _categories(*attrs: str):
    """The trace categories of the program scopes named by ``attrs``
    (attributes of ``repro.scopes``), or None where the program lacks
    one of them."""
    cats = [getattr(S.names, a, None) for a in attrs]
    return None if None in cats else cats


def _step_ms(ctx, program: str, *attrs: str):
    s = S.summary(ctx)
    cats = _categories(*attrs)
    if s is None or cats is None:
        return None
    split, _ = s.step_split(program)
    seen = [split[c] for c in cats if c in split]
    return 1e3 * sum(seen) if seen else None


def decode_ssm_mixer_ms(ctx):
    """Mean device ms per decode step run with innermost scope the
    mixer's (its state's operations apart)."""
    return _step_ms(ctx, S.DECODE_PROGRAM, "SSM_MIXER")


def decode_ssm_state_ms(ctx):
    """Mean device ms per decode step run with innermost scope the
    state's: the conv-state shift, the state update and its read-out,
    and the layer's state out of and back into the cache's stack."""
    return _step_ms(ctx, S.DECODE_PROGRAM, "SSM_STATE")


def prefill_ssm_ms(ctx):
    """Mean device ms per chunk step run under either mixer scope."""
    return _step_ms(ctx, S.CHUNK_PROGRAM, "SSM_MIXER", "SSM_STATE")


def state_bytes_per_slot(model: dict) -> int:
    """Bytes of one slot's float32 SSD state over every Mamba2 layer:
    layers x heads x head dim x state size x 4."""
    ssm = model["ssm"]
    d_in = ssm["expand"] * model["d_model"]
    layers = list(model.get("layer_types", ())).count("mamba")
    return layers * d_in * ssm["d_state"] * F32_BYTES


def _decode_actives(ctx) -> list[int]:
    """Active slots of each decode step call in the traced stretch, in
    order (from the recorded ticks)."""
    a, b = ctx.served.trace_ticks
    return [int(sum(t.rows)) for t in ctx.served.ticks[a:b]
            if t.phase == "decode"]


def ssm_state_roofline(ctx):
    """Per decode step run: the bytes the state update must move (its
    active slots' SSD state read once and written once, never the idle
    rows') over the run's ``SSM_STATE`` device time, over the chip's HBM
    bytes per second; the mean over runs, in per cent.  Runs pair with
    the recorded decode calls in order, from the last."""
    s = S.summary(ctx)
    cats = _categories("SSM_STATE")
    per_slot = state_bytes_per_slot(ctx.conf["model"])
    if s is None or cats is None or not per_slot:
        return None
    runs = sorted(s.runs(S.DECODE_PROGRAM), key=lambda r: r.start)
    starts = [o.start for o in s.ops]
    times = []
    for r in runs:
        t = 0.0
        for o in s.ops[bisect.bisect_left(starts, r.start):]:
            if o.start >= r.end:
                break
            if o.plane == r.plane and o.end <= r.end \
                    and S.category(o) == cats[0]:
                t += o.dur
        times.append(t)
    actives = _decode_actives(ctx)
    n = min(len(times), len(actives))
    peak = ctx.peaks["hbm_bytes_per_s"]
    shares = [2 * act * per_slot / t / peak
              for t, act in zip(times[len(times) - n:],
                                actives[len(actives) - n:])
              if t > 0 and act > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
