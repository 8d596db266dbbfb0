"""Reduce a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: for each TPU
the operations the device ran (the "XLA Ops" line), and the host spans the
harness recorded.  The rest are pure functions of event lists, tested on
synthetic traces: the union of busy intervals, the split of a step's device
time by the profiler's name stack, collective time that no compute hides,
and the longest idle gaps named by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds on the profiler's clock
    dur: float
    stats: tuple = ()     # (key, value) pairs as the profiler gives them

    @property
    def end(self) -> float:
        return self.start + self.dur

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Trace:
    devices: dict          # device plane name -> list[Event] of its ops
    modules: dict          # device plane name -> list[Event] of its programs
    host: list             # the harness's host spans
    window: tuple          # (start, end) of the traced window
    op_names: dict = dataclasses.field(default_factory=dict)  # HLO name -> name stack


HOST_SPANS = ("feed", "dispatch", "block", "loss")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def hlo_op_names(hlo_text: str) -> dict:
    """HLO instruction name -> the JAX name stack in its metadata, from a
    compiled program's text (the trace names ops by instruction only)."""
    return dict(re.findall(r'%(\S+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
                           hlo_text))


def module_name(hlo_text: str) -> str:
    """``jit_step_fn`` of a compiled program's ``HloModule jit_step_fn, ...``:
    the name its runs carry in the trace's programs line."""
    return re.match(r"HloModule ([^\s,]+)", hlo_text).group(1)


def instruction(op: Event) -> str:
    """``fusion.12`` of an event named ``%fusion.12 = f32[...] fusion(...)``."""
    head = op.name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(trace_dir: str, op_names: dict | None = None) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods += [_event(e) for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda e: e.start)
            modules[plane.name] = sorted(mods, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [_event(e) for e in line.events if e.name in HOST_SPANS]
    host.sort(key=lambda e: e.start)
    if host:
        window = (host[0].start, max(e.end for e in host))
    else:
        window = (min(o.start for ops in devices.values() for o in ops),
                  max(o.end for ops in devices.values() for o in ops))
    return Trace(devices=devices, modules=modules, host=host, window=window,
                 op_names=op_names or {})


def _event(e) -> Event:
    stats = tuple((str(k), v) for k, v in dict(e.stats).items())
    return Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9, stats)


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_seconds(ops, window) -> float:
    return covered(clip([(o.start, o.end) for o in ops], *window))


def overlap(a, b) -> float:
    """Total length of the intersection of two interval sets."""
    a, b = union(a), union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|"
                        r"all-to-all|allgather|allreduce|reducescatter", re.I)


def is_collective(op: Event) -> bool:
    return bool(COLLECTIVE.search(op.name))


def innermost(ops) -> list:
    """The ops that enclose no other op (a ``while`` op encloses its body):
    in start order, an op whose successor starts before it ends holds it."""
    ordered = sorted(ops, key=lambda o: (o.start, -o.dur))
    return [o for o, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt.start >= o.end]


def exposed_collective_seconds(ops) -> float:
    """Collective time during which no other operation runs on the device
    (ops that only enclose others, as a loop does, are not counted as
    running)."""
    coll = [(o.start, o.end) for o in ops if is_collective(o)]
    comp = [(o.start, o.end) for o in innermost([o for o in ops if not is_collective(o)])]
    return covered(coll) - overlap(coll, comp)


def within(ops, spans) -> list:
    """Ops that start inside one of ``spans`` (both sorted by start)."""
    out, j = [], 0
    for o in ops:
        while j < len(spans) and spans[j].end <= o.start:
            j += 1
        if j < len(spans) and spans[j].start <= o.start:
            out.append(o)
    return out


def phase(op: Event, op_names: dict) -> str:
    """``backward`` for ops under a transpose, ``forward`` under a jvp (and
    not a transpose), ``optimizer`` for the rest of the step."""
    stack = op_names.get(instruction(op), "")
    if "transpose" in stack:
        return "backward"
    if "jvp" in stack:
        return "forward"
    return "optimizer"


def self_times(ops) -> list:
    """(op, self seconds) for every op: its duration less that of the ops
    nested inside it (a ``while`` op encloses the ops of its body)."""
    out, stack = [], []
    for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
        while stack and o.start >= stack[-1][0].end:
            stack.pop()
        entry = [o, o.dur]
        if stack:
            stack[-1][1] -= o.dur
        out.append(entry)
        stack.append(entry)
    return [(o, max(t, 0.0)) for o, t in out]


def time_by(ops, key) -> dict:
    """Self seconds summed by ``key(op)``."""
    out = {}
    for o, t in self_times(ops):
        k = key(o)
        out[k] = out.get(k, 0.0) + t
    return out


def idle_gaps(ops, host, window, top: int = 10) -> list:
    """The longest gaps between device ops inside the window, each named by
    the host span that covers the most of it (``other`` where none does)."""
    busy = union(clip([(o.start, o.end) for o in ops], *window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for lo, hi in gaps:
        best, best_len = "other", 0.0
        for h in host:
            if h.end <= lo or h.start >= hi:
                continue
            ov = min(hi, h.end) - max(lo, h.start)
            if ov > best_len:
                best, best_len = h.name, ov
        named.append([best, hi - lo])
    return sorted(named, key=lambda x: -x[1])[:top]
