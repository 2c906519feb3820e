"""Drives a ``DecodeServer`` through a cell's traffic and stamps every
request and token on the host clock.

The server's public API is all that is called: ``submit`` between ticks
and ``tick``.  Each ``tick`` ends in a blocking host read of the step's
results, so ``time.perf_counter()`` taken when it returns is a sound
time for the tokens that tick produced.  A request's due time is the
schedule's, not the time ``submit`` ran; the difference is the
generator's lateness, which is reported.

``Recorder`` wraps the server's two compiled steps and keeps, for every
call, which request sat in which row, the rows' tokens and the step's
own dispatch counts, with the KV pages the server held.  The
correctness check replays the ticks from it, and the per-layer readers
count work from it.  It keeps device arrays
and reads nothing back while the window runs.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from chipbench.traffic import Item, Traffic


@dataclasses.dataclass
class Tick:
    """One call of a compiled step."""

    phase: str            # "decode" or "chunk"
    rids: list            # request id per batch row, -1 where empty
    tokens: object        # (B, 1) or (B, S) int32
    rows: object          # decode: (B,) active mask; chunk: (B,) n_valid
    counts: object        # (n + 1,) routed rows per class
    dispatched: object    # (n + 1,) rows executed per class
    pages: int = 0        # KV pages the server held when the step ran


class Recorder:
    def __init__(self, server):
        self.server = server
        self.ticks: list[Tick] = []
        server.decode = self._wrap(server.decode, "decode")
        server.chunk = self._wrap(server.chunk, "chunk")

    def _wrap(self, step, phase):
        def recorded(params, cache, tokens, rows, *rest, **kw):
            rids = [-1 if s is None else s.rid for s in self.server.slots]
            out = step(params, cache, tokens, rows, *rest, **kw)
            m = out[-1]
            self.ticks.append(Tick(phase, rids, tokens, rows,
                                   m["class_counts"], m["dispatched"],
                                   self.server.pages_in_use))
            return out
        return recorded

    def to_host(self) -> list[Tick]:
        """The log with every array read back (after the window)."""
        arrays = jax.device_get([(t.tokens, t.rows, t.counts, t.dispatched)
                                 for t in self.ticks])
        return [Tick(t.phase, t.rids, np.asarray(a[0]), np.asarray(a[1]),
                     np.asarray(a[2]), np.asarray(a[3]), t.pages)
                for t, a in zip(self.ticks, arrays)]


class Stamp:
    """One request as the benchmark saw it."""

    __slots__ = ("item", "req", "due", "submitted", "times", "rejected")

    def __init__(self, item: Item, req, due: float, submitted: float):
        self.item, self.req = item, req
        self.due, self.submitted = due, submitted
        self.times: list[float] = []
        self.rejected = False

    @property
    def finished(self) -> bool:
        return self.req.done and not self.req.aborted and not self.rejected


class Client:
    """Submits a mix's requests and ticks the server."""

    def __init__(self, server, traffic: Traffic):
        self.server, self.traffic = server, traffic
        self.stamps: list[Stamp] = []
        self._inflight: list[Stamp] = []
        self._next = 0
        self.origin = None

    def _submit(self, item: Item, due: float):
        from repro.runtime.server import Request
        req = Request(rid=item.rid, prompt=item.prompt, max_new=item.max_new)
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            st = Stamp(item, req, due, time.perf_counter())
            try:
                self.server.submit(req)
                self._inflight.append(st)
            except ValueError:
                st.rejected = True
        self.stamps.append(st)

    def _arrivals(self, now: float):
        """Submit every request due by ``now``; returns the next due time."""
        while True:
            it = self.traffic.item(self._next)
            due = self.origin + it.due_s
            if due > now:
                return due
            self._submit(it, due)
            self._next += 1

    def _tick(self):
        with jax.profiler.TraceAnnotation("chipbench.tick"):
            self.server.tick()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.stamp"):
            keep = []
            for st in self._inflight:
                n = len(st.req.out)
                if n > len(st.times):
                    st.times.extend([t] * (n - len(st.times)))
                if not st.req.done:
                    keep.append(st)
            self._inflight = keep

    def switch(self, traffic: Traffic):
        """Draw further requests from ``traffic``, whose schedule starts
        now; requests already submitted carry on."""
        self.traffic, self._next, self.origin = traffic, 0, None

    def run(self, until: float):
        """Serve until the host clock passes ``until``; the tick running
        then completes.  The first call after a switch fixes the
        schedule's origin."""
        if self.origin is None:
            self.origin = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now >= until:
                return now
            next_due = self._arrivals(now)
            if self.server.queue or any(s is not None
                                        for s in self.server.slots):
                self._tick()
            else:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    time.sleep(max(0.0, min(next_due, until) - now))


def compile_warmup(server, vocab: int, chunk: int, seed: int):
    """Serve two short requests to the end, so that every program the
    window runs (the chunk step, the decode step and the eager updates
    around them) is compiled or loaded from the cache before it starts."""
    from repro.runtime.server import Request
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=-2 - i,
                    prompt=rng.integers(0, vocab, chunk + 2, dtype=np.int32),
                    max_new=2) for i in range(2)]
    for r in reqs:
        server.submit(r)
    while server.queue or any(s is not None for s in server.slots):
        server.tick()


@dataclasses.dataclass
class WindowNumbers:
    """What the window measured on the host clock."""

    seconds: float
    ttft_s: list            # requests due in the window
    itl_s: list             # gaps with both tokens in the window
    generated: int          # tokens sampled in the window
    attempted: int
    failed: int
    lateness_s: list        # submit time minus due time


def window_numbers(stamps: list[Stamp], t0: float,
                   t1: float) -> WindowNumbers:
    ttft, itl, gen, attempted, failed, late = [], [], 0, 0, 0, []
    for st in stamps:
        times = st.times
        gen += sum(1 for t in times if t0 <= t <= t1)
        itl.extend(b - a for a, b in zip(times, times[1:]) if a >= t0
                   and b <= t1)
        if not t0 <= st.due < t1:
            continue
        attempted += 1
        failed += st.rejected or st.req.aborted
        late.append(st.submitted - st.due)
        first = times[0] if times and times[0] <= t1 else t1
        ttft.append(first - st.due)
    return WindowNumbers(t1 - t0, ttft, itl, gen, attempted, failed, late)
