"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

A trace has one plane per device (``/device:TPU:<n>``) with a line of XLA
programs ("XLA Modules") and a line of XLA operations ("XLA Ops"), and
host planes whose lines hold the benchmark's own ``bench/*`` spans
(``jax.profiler.TraceAnnotation``).  Everything here works on plain
``Event`` tuples, so the tests can feed it a recorded trace or made-up
events alike.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
BENCH_SPAN = re.compile(r"^bench/")


class Event(NamedTuple):
    name: str
    start: float          # seconds, on the trace's common clock
    dur: float            # seconds
    meta: str = ""        # the event's string stats, joined


@dataclasses.dataclass
class Device:
    modules: list          # Event per program execution
    ops: list              # Event per operation


@dataclasses.dataclass
class Trace:
    devices: list          # Device per chip, in plane order
    spans: list            # host Event per bench/* span

    def window(self) -> tuple[float, float]:
        """The traced window: from the first bench span's start to the
        last one's end (the whole device timeline without spans)."""
        if self.spans:
            return (min(e.start for e in self.spans),
                    max(e.start + e.dur for e in self.spans))
        evs = [e for d in self.devices for e in d.ops]
        return (min(e.start for e in evs), max(e.start + e.dur for e in evs))


def _stats_text(ev) -> str:
    out = []
    for k, v in getattr(ev, "stats", ()) or ():
        if isinstance(v, str) and v:
            out.append(f"{k}={v}")
    return " ".join(out)


CPU_OPS = re.compile(r"^tf_XLAPjRtCpuClient")
CPU_PROGRAM = re.compile(r"^PjitFunction\((.*)\)$")


def load(path: str, cpu: bool = False) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``path`` (a profiler log
    directory or the file itself).  ``cpu``: a trace recorded on the CPU,
    whose operations run on host threads; they become one device, and the
    host's dispatch of each jitted program stands for its execution."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    devices, spans, cpu_ops, cpu_mods = [], [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                target = (mods if line.name == MODULE_LINE else
                          ops if line.name == OP_LINE else None)
                if target is None:
                    continue
                for ev in line.events:
                    target.append(Event(ev.name, ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9,
                                        _stats_text(ev)))
            devices.append(Device(mods, ops))
        else:
            for line in plane.lines:
                for ev in line.events:
                    e = Event(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                    if BENCH_SPAN.match(ev.name):
                        spans.append(e)
                    elif cpu and CPU_OPS.match(line.name):
                        cpu_ops.append(e)
                    elif cpu and CPU_PROGRAM.match(ev.name):
                        cpu_mods.append(e._replace(
                            name=CPU_PROGRAM.match(ev.name).group(1)))
    if cpu:
        devices.append(Device(cpu_mods, cpu_ops))
    return Trace(devices, spans)


# ---------------------------------------------------------------------------
# intervals

def union(intervals: Iterable[tuple[float, float]]) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one event runs."""
    return sum(e - s for s, e in clip(union(
        (ev.start, ev.start + ev.dur) for ev in events), lo, hi))


def gaps(events: Iterable[Event], lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi] between the events."""
    busy = clip(union((ev.start, ev.start + ev.dur) for ev in events), lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_share(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the chips, window seconds)."""
    lo, hi = trace.window()
    busy = [busy_seconds(d.ops, lo, hi) for d in trace.devices]
    return sum(busy) / len(busy), hi - lo


# ---------------------------------------------------------------------------
# programs and operations

def in_window(events, trace: Trace):
    lo, hi = trace.window()
    return [e for e in events if lo <= e.start + 0.5 * e.dur <= hi]


def module_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """Device seconds and executions of the programs whose name matches
    ``pattern``, in the window, averaged over the chips."""
    rx = re.compile(pattern)
    secs, calls = [], []
    for d in trace.devices:
        evs = [e for e in in_window(d.modules, trace) if rx.search(e.name)]
        secs.append(sum(e.dur for e in evs))
        calls.append(len(evs))
    return sum(secs) / len(secs), max(calls)


_HLO = re.compile(r"^%?(?P<inst>[\w.\-]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])"
                  r".*?\b(?P<op>[a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_SUFFIX = re.compile(r"[.\-_]?\d+$")
CONTAINERS = ("while", "conditional", "call")


def op_family(name: str) -> str:
    """An operation's kind without its instance number, so that calls of
    one kind add up.  Device traces name an operation by its HLO text
    (``%fusion.711 = bf16[2,2048,4096]{...} fusion(...), kind=kOutput``):
    that gives "fusion:kOutput bf16[2,2048,4096]"; other names lose their
    numeric suffix (fusion.12 → fusion)."""
    m = _HLO.match(name)
    if not m:
        return _SUFFIX.sub("", name)
    op = m.group("op")
    kind = _KIND.search(name)
    if kind:
        op = f"{op}:{kind.group(1)}"
    return f"{op} {m.group('type').lstrip('(')}"


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device time
    in the window (chip 0), by ``op_family``; loop operations, which
    enclose others, are left out."""
    tot: dict = defaultdict(float)
    for e in in_window(trace.devices[0].ops, trace):
        fam = op_family(e.name)
        if fam.split(" ")[0] not in CONTAINERS:     # loops hold other ops
            tot[fam] += e.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[host span, seconds], ...]: the longest idle gaps of chip 0 in the
    window, each named by the innermost bench span open at its midpoint
    (or "no span")."""
    lo, hi = trace.window()
    out = []
    for s, e in gaps(trace.devices[0].ops, lo, hi):
        mid = 0.5 * (s + e)
        open_ = [sp for sp in trace.spans
                 if sp.start <= mid <= sp.start + sp.dur]
        name = (min(open_, key=lambda sp: sp.dur).name if open_
                else "no span")
        out.append([name, e - s])
    out.sort(key=lambda kv: -kv[1])
    return out[:n]
