"""Reduction from a profiler trace to the numbers the per-layer readers
take: the device's busy time over a window, device time per program and
per operation, and the idle gaps with the host span that covered each.

The trace is JAX's own ``.xplane.pb``, read with
``jax.profiler.ProfileData``.  On a TPU every chip is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
run and whose line ``XLA Modules`` holds one event per program run; the
benchmark's host spans (``chipbench.*`` ``TraceAnnotation`` s) sit on the
host plane's Python thread.  Times in the file are nanoseconds from the
start of the trace, on one clock for host and device.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in ``peaks.json``."""


def peaks_for(kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks row of ``kind``; a device not in the table is an error,
    never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise UnknownDevice(f"device kind {kind!r} has no row in {path.name}"
                            f" (rows: {sorted(table)})")
    return table[kind]


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # seconds from the start of the trace
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_events(path) -> list[Event]:
    """Every event with a duration, from every plane and line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    s = ev.start_ns * 1e-9
                    out.append(Event(plane.name, line.name, ev.name, s,
                                     s + ev.duration_ns * 1e-9))
    return out


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def short(name: str) -> str:
    """An operation's own name: its trace name up to the HLO text."""
    return name.split(" = ", 1)[0]


class TraceSummary:
    """The events of one traced window, grouped as the readers use them.

    ``device_planes`` selects the chips' planes; ``op_line`` and
    ``module_line`` name their operation and program lines.  The window
    is the host span ``WINDOW_SPAN``; everything is clipped to it."""

    def __init__(self, events: list[Event], *,
                 device_prefix: str = DEVICE_PLANE_PREFIX,
                 op_line: str = OP_LINE, module_line: str = MODULE_LINE):
        windows = [e for e in events if e.name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' "
                             "spans; expected one")
        self.lo, self.hi = windows[0].start, windows[0].end
        self.planes = sorted({e.plane for e in events
                              if e.plane.startswith(device_prefix)})
        self.ops = [e for e in events if e.plane in self.planes
                    and e.line == op_line and e.end > self.lo
                    and e.start < self.hi]
        self.modules = [e for e in events if e.plane in self.planes
                        and e.line == module_line and e.end > self.lo
                        and e.start < self.hi]
        self.spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
                      and e.name != WINDOW_SPAN]

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.planes:
            return 0.0
        return sum(union_length([(e.start, e.end) for e in self.ops
                                 if e.plane == p], self.lo, self.hi)
                   for p in self.planes) / len(self.planes)

    def idle_frac(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_calls(self, name_part: str) -> list[Event]:
        """Runs of the programs whose name contains ``name_part`` that lie
        wholly inside the window."""
        return [e for e in self.modules if name_part in e.name
                and e.start >= self.lo and e.end <= self.hi]

    def top_ops(self, n: int = 10) -> list[list]:
        """The operations that took most device time, by own name; loops
        and conditionals are left out, since their bodies' operations are
        events of their own."""
        tot: dict[str, float] = {}
        for e in self.ops:
            name = short(e.name)
            if name.lstrip("%").startswith(("while", "conditional", "call")):
                continue
            d = min(e.end, self.hi) - max(e.start, self.lo)
            tot[name] = tot.get(name, 0.0) + d
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest stretches with no operation on the first chip,
        each named by the innermost benchmark span covering its middle
        ("none" where no span does)."""
        if not self.planes:
            return []
        p0 = self.planes[0]
        found = []
        for s, e in gaps([(o.start, o.end) for o in self.ops
                          if o.plane == p0], self.lo, self.hi):
            mid = 0.5 * (s + e)
            cover = [sp for sp in self.spans if sp.start <= mid < sp.end]
            name = min(cover, key=lambda sp: sp.dur).name if cover else "none"
            found.append([name, e - s])
        return sorted(found, key=lambda g: -g[1])[:n]
