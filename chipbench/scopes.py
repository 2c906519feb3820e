"""Reduction of a traced window by the program's own names: the decode
step split by device scope, the device's idle time put down to the host
span of ``DecodeServer.tick`` it fell in, and the request stamps.

The names are the program's (``repro/scopes.py``).  A program that has
none (it predates them) gives every reader here nothing to read.

On a TPU, each operation's framework op path (``tf_op``, such as
``jit(decode_step)/while/body/closed_call/attention/kv_gather/gather:``)
is a stat of the operation's metadata on the chip's plane, which
``jax.profiler.ProfileData`` does not expose, so this module reads the
``.xplane.pb`` with its own message classes (only the fields it needs).
An operation belongs to the innermost program scope on its path (the
weight-switch kernels' ``pallas_call`` names count as scopes); a fusion
carries its root's path.  Loops and conditionals are left out, as in
``trace.TraceSummary.top_ops``: their bodies' operations are events of
their own.  Operations under no scope (norms, residuals, the embedding,
loop control) are the remainder.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools

from chipbench import trace as T

try:
    from repro import scopes as names
except ImportError:          # a program without named scopes
    names = None

DECODE_PROGRAM = "jit_decode_step"
CHUNK_PROGRAM = "jit_prefill_chunk_step"
REMAINDER = "remainder"
OUTSIDE = "outside"          # idle time under no serve.* span
_LOOPS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation, or one program run."""

    plane: str
    name: str               # the event's name (HLO text for an operation)
    path: str               # the framework op path; "" where none
    start: float            # seconds, one clock for host and device
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


# -- reading the file ------------------------------------------------------

_FIELDS = {   # message: [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, "msg", True, "XPlane")],
    "XPlane": [("name", 2, "str", False, None),
               ("lines", 3, "msg", True, "XLine"),
               ("event_metadata", 4, "msg", True, "EventMetadataEntry"),
               ("stat_metadata", 5, "msg", True, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "i64", False, None),
                           ("value", 2, "msg", False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "i64", False, None),
                          ("value", 2, "msg", False, "XStatMetadata")],
    "XLine": [("name", 2, "str", False, None),
              ("timestamp_ns", 3, "i64", False, None),
              ("events", 4, "msg", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "i64", False, None),
               ("offset_ps", 2, "i64", False, None),
               ("duration_ps", 3, "i64", False, None)],
    "XEventMetadata": [("id", 1, "i64", False, None),
                       ("name", 2, "str", False, None),
                       ("stats", 5, "msg", True, "XStat")],
    "XStat": [("metadata_id", 1, "i64", False, None),
              ("str_value", 5, "str", False, None),
              ("ref_value", 7, "u64", False, None)],
    "XStatMetadata": [("id", 1, "i64", False, None),
                      ("name", 2, "str", False, None)],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The message class of an XSpace (TensorBoard's xplane schema, the
    fields above only; a map is a repeated entry on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"str": F.TYPE_STRING, "i64": F.TYPE_INT64,
             "u64": F.TYPE_UINT64, "msg": F.TYPE_MESSAGE}
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind, repeated, ref in fields:
            f = m.field.add(name=name, number=number, type=kinds[kind],
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".chipbench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def _path_of(md, stat_names: dict) -> str:
    for st in md.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def read_scoped(path, span_names, *,
                device_prefix: str = T.DEVICE_PLANE_PREFIX):
    """``(ops, modules, spans)`` of a trace file: the chips' operations
    with their op paths, their program runs, and the host spans named in
    ``span_names``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, spans = [], [], []
    # times from the first line's start, so that sums of short operations
    # keep their nanoseconds
    origin = min((ln.timestamp_ns for pl in space.planes for ln in pl.lines
                  if ln.events), default=0)
    for plane in space.planes:
        device = plane.name.startswith(device_prefix)
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        paths: dict[int, str] = {}
        for line in plane.lines:
            if device and line.name not in (T.OP_LINE, T.MODULE_LINE):
                continue
            base = (line.timestamp_ns - origin) * 1e-9
            for ev in line.events:
                if ev.duration_ps <= 0:
                    continue
                md = meta.get(ev.metadata_id)
                name = md.name if md is not None else ""
                s = base + ev.offset_ps * 1e-12
                e = s + ev.duration_ps * 1e-12
                if not device:
                    if name in span_names:
                        spans.append(Span(name, s, e))
                elif line.name == T.MODULE_LINE:
                    modules.append(Op(plane.name, name, "", s, e))
                else:
                    if ev.metadata_id not in paths:
                        paths[ev.metadata_id] = "" if md is None \
                            else _path_of(md, stat_names)
                    ops.append(Op(plane.name, name, paths[ev.metadata_id],
                                  s, e))
    return ops, modules, spans


# -- attribution -----------------------------------------------------------

def category(op: Op) -> str:
    """The innermost program scope on the op path (either weight-switch
    kernel counts as ``SWITCH_KERNEL``), else the remainder."""
    for part in reversed(op.path.split("/")):
        if part in names.SWITCH_KERNELS:
            return names.SWITCH_KERNEL
        if part in names.DEVICE_SCOPES:
            return part
    return REMAINDER


def _is_loop(op: Op) -> bool:
    return T.short(op.name).lstrip("%").startswith(_LOOPS)


def attribute_idle(gaps, spans) -> dict:
    """Seconds of ``gaps`` under each span, by the innermost (the latest
    opened of those still open) span covering each stretch; ``OUTSIDE``
    where none does.  Spans come from one thread, so they nest."""
    pts = []
    for sp in spans:
        pts += [(sp.start, 1, sp), (sp.end, 0, sp)]
    for a, b in gaps:
        pts += [(a, 3, None), (b, 2, None)]
    pts.sort(key=lambda p: (p[0], p[1]))
    out: dict[str, float] = {}
    stack, idle, t = [], 0, None
    for when, kind, sp in pts:
        if idle and when > t:
            key = stack[-1].name if stack else OUTSIDE
            out[key] = out.get(key, 0.0) + when - t
        t = when
        if kind == 1:
            stack.append(sp)
        elif kind == 0:
            stack.remove(sp)
        else:
            idle += 1 if kind == 3 else -1
    return out


class Cover:
    """Sorted disjoint intervals, asked how much of a stretch they cover."""

    def __init__(self, merged):
        self.merged = merged
        self.starts = [s for s, _ in merged]

    def __call__(self, lo: float, hi: float) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        tot = 0.0
        for s, e in self.merged[i:]:
            if s >= hi:
                break
            tot += max(0.0, min(e, hi) - max(s, lo))
        return tot


class ScopeSummary:
    """The traced window (the host span ``chipbench.window``) seen through
    the program's scopes and spans."""

    def __init__(self, ops, modules, spans):
        windows = [s for s in spans if s.name == T.WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"trace holds {len(windows)} "
                             f"'{T.WINDOW_SPAN}' spans; expected one")
        self.lo, self.hi = windows[0].start, windows[0].end
        self.planes = sorted({o.plane for o in ops})
        self.ops = sorted((o for o in ops if o.end > self.lo
                           and o.start < self.hi and not _is_loop(o)),
                          key=lambda o: o.start)
        self.modules = [m for m in modules if m.end > self.lo
                        and m.start < self.hi]
        self.spans = [s for s in spans if s.name != T.WINDOW_SPAN]
        p0 = self.planes[0] if self.planes else None
        self.busy = T.merge([(o.start, o.end) for o in self.ops
                             if o.plane == p0], self.lo, self.hi)
        self._busy = Cover(self.busy)

    def runs(self, program: str) -> list[Op]:
        """Runs of ``program`` wholly inside the window."""
        return [m for m in self.modules if m.name.startswith(program + "(")
                and m.start >= self.lo and m.end <= self.hi]

    def step_split(self, program: str) -> tuple[dict, int]:
        """Mean device seconds per run of ``program`` by category (the
        device scopes, ``SWITCH_KERNEL`` and ``REMAINDER``), and the
        number of runs."""
        runs = self.runs(program)
        tot: dict[str, float] = {}
        starts = [o.start for o in self.ops]
        for r in runs:
            i = bisect.bisect_left(starts, r.start)
            for o in self.ops[i:]:
                if o.start >= r.end:
                    break
                if o.plane == r.plane and o.end <= r.end:
                    c = category(o)
                    tot[c] = tot.get(c, 0.0) + o.dur
        return ({k: v / len(runs) for k, v in tot.items()} if runs else {},
                len(runs))

    def ticks(self) -> list[Span]:
        """``serve.tick`` spans wholly inside the window."""
        return [s for s in self.spans if s.name == names.TICK
                and s.start >= self.lo and s.end <= self.hi]

    def tick_idle(self) -> list[float]:
        """Per tick, the seconds no operation ran on the first chip."""
        return [t.dur - self._busy(t.start, t.end)
                for t in self.ticks()]

    def idle_split(self) -> tuple[dict, float, float]:
        """The window's idle seconds on the first chip by innermost
        ``serve.*`` span, their total, and the part that fell between
        the operations of a running program."""
        gaps = T.gaps(self.busy, self.lo, self.hi)
        serve = [s for s in self.spans if s.name in names.TICK_SPANS]
        split = attribute_idle(gaps, serve)
        runs = Cover(T.merge([(m.start, m.end) for m in self.modules
                              if m.plane == self.planes[0]],
                             self.lo, self.hi)) if self.planes else None
        in_runs = sum(runs(a, b) for a, b in gaps) if runs else 0.0
        return split, sum(e - s for s, e in gaps), in_runs


# -- the readers' arithmetic -----------------------------------------------

def _fmt(d: dict, scale: float = 1e3) -> str:
    return ", ".join(f"{k} {v * scale!r}" for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1]))


def report(s: ScopeSummary):
    """Earlier ``[trace]`` lines: each step's split with its remainder,
    the idle split by span, and the host time per tick."""
    for program in (DECODE_PROGRAM, CHUNK_PROGRAM):
        split, n = s.step_split(program)
        runs = s.runs(program)
        if n:
            print(f"[trace] {program} split, ms per run over {n} runs: "
                  f"{_fmt(split)}; sum {sum(split.values()) * 1e3!r}; "
                  f"program run {sum(r.dur for r in runs) / n * 1e3!r}")
    split, idle, in_runs = s.idle_split()
    child = sum(v for k, v in split.items()
                if k in names.TICK_SPANS and k != names.TICK)
    print(f"[trace] device idle by innermost serve span, ms: {_fmt(split)}; "
          f"idle {idle * 1e3!r} ms, {child / idle if idle else 0.0!r} of it "
          f"in a child span of {names.TICK}, {in_runs * 1e3!r} ms between "
          "the operations of a running program")
    ticks = s.ticks()
    if ticks:
        print(f"[trace] {len(ticks)} {names.TICK} spans: mean "
              f"{sum(t.dur for t in ticks) / len(ticks) * 1e3!r} ms host "
              f"time, {sum(s.tick_idle()) / len(ticks) * 1e3!r} ms of it "
              "with the chip idle")


@functools.lru_cache(maxsize=1)
def _summary_of(trace_dir: str) -> ScopeSummary:
    s = ScopeSummary(*read_scoped(T.find_xplane(trace_dir),
                                  set(names.TICK_SPANS) | {T.WINDOW_SPAN}))
    report(s)
    return s


def summary(ctx) -> ScopeSummary | None:
    """The traced window's summary, read once per trace and reported
    when first read; None without a trace or without program names."""
    d = ctx.served.trace_dir
    if names is None or d is None:
        return None
    return _summary_of(d)


def _decode_reader(*scopes: str):
    """A reader of the mean device ms per decode step run in the program
    scopes named by ``scopes`` (attributes of ``repro.scopes``); it reads
    None where no run holds an operation of any of them."""
    def read(ctx):
        s = summary(ctx)
        if s is None:
            return None
        split, _ = s.step_split(DECODE_PROGRAM)
        seen = [split[c] for c in (getattr(names, a) for a in scopes)
                if c in split]
        return 1e3 * sum(seen) if seen else None
    return read


decode_kv_ms = _decode_reader("KV_WRITE", "KV_GATHER")
decode_attention_ms = _decode_reader("ATTENTION")
decode_mcma_plan_ms = _decode_reader("MCMA_PLAN")
decode_switch_kernel_ms = _decode_reader("SWITCH_KERNEL")
decode_exact_ffn_ms = _decode_reader("EXACT_FFN")
decode_unembed_ms = _decode_reader("UNEMBED")


def tick_host_idle_ms(ctx):
    """Mean ms per ``serve.tick`` span in the window with no operation
    running on the chip."""
    s = summary(ctx)
    idle = s.tick_idle() if s is not None else []
    return 1e3 * sum(idle) / len(idle) if idle else None


def _stamp_mean_ms(ctx, start: str, end: str):
    """Mean of ``end - start`` over requests whose ``end`` stamp lies in
    the window; None where the program keeps no such stamps."""
    t0, t1 = ctx.served.t0, ctx.served.t1
    waits = []
    for st in ctx.served.stamps:
        a, b = getattr(st.req, start, None), getattr(st.req, end, None)
        if a is not None and b is not None and t0 <= b < t1:
            waits.append(b - a)
    return 1e3 * sum(waits) / len(waits) if waits else None


def queue_wait_ms(ctx):
    return _stamp_mean_ms(ctx, "arrival_s", "admitted_s")


def prefill_wait_ms(ctx):
    return _stamp_mean_ms(ctx, "admitted_s", "first_token_s")
