"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, time per program and per kernel, the
top device operations by self time, and the idle gaps labelled by the
benchmark's own host spans.

Layout of a TPU trace as JAX writes it: a plane ``/device:TPU:<n>`` per
chip, whose line ``XLA Modules`` holds one event per program execution and
``XLA Ops`` one per operation (a ``while`` op's event encloses its body's
ops; a Pallas kernel's event is named ``%<kernel>`` or ``%<kernel>.<n>``);
a plane ``/host:CPU`` whose lines hold the host threads' events, among
them the ``jax.profiler.TraceAnnotation`` spans.  All times are
nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[DeviceTrace]
    spans: list[Event]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def find_trace_file(directory) -> pathlib.Path:
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path) -> Trace:
    """Read a trace file into device ops and modules and the host spans
    named ``bench.*``.  The window is the ``bench.window`` span, or the span
    of all device events where it is missing."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [Event(_short(e.name), float(e.start_ns), float(e.duration_ns))
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules).extend(evs)
            devices.append(DeviceTrace(ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(Event(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    window = [s for s in spans if s.name == WINDOW_SPAN]
    if window:
        lo, hi = window[0].start, window[0].end
    else:
        evs = [e for d in devices for e in d.ops + d.modules]
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    return Trace(devices, sorted(spans, key=lambda s: s.start), (lo, hi))


def busy_intervals(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals inside [lo, hi], merged and sorted."""
    ivs = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                 if e.dur > 0 and e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    lo, hi = trace.window
    per = [sum(b - a for a, b in busy_intervals(d.ops, lo, hi)) for d in trace.devices]
    return 1e-9 * sum(per) / len(per)


def _is_kernel(name: str, kernel: str) -> bool:
    return re.fullmatch(rf"%{re.escape(kernel)}(\.\d+)?", name) is not None


def kernel_events(trace: Trace, kernel: str) -> list[Event]:
    lo, hi = trace.window
    return [e for d in trace.devices for e in d.ops
            if _is_kernel(e.name, kernel) and e.start >= lo and e.end <= hi]


def programs_with(trace: Trace, kernel: str) -> list[Event]:
    """Program executions inside the window that ran ``kernel``: the device
    time of the jitted step that holds it."""
    lo, hi = trace.window
    out = []
    for d in trace.devices:
        marks = sorted(e.start for e in d.ops if _is_kernel(e.name, kernel))
        j = 0
        for m in sorted(d.modules, key=lambda e: e.start):
            while j < len(marks) and marks[j] < m.start:
                j += 1
            if j < len(marks) and marks[j] <= m.end and m.start >= lo and m.end <= hi:
                out.append(m)
    return out


def self_times(events: list[Event]) -> dict[str, float]:
    """Seconds per op name of time not covered by an enclosed op (a
    ``while`` op's body ops are its children)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [event, child time]

    def close(item):
        ev, child = item
        out[ev.name] = out.get(ev.name, 0.0) + max(0.0, ev.dur - child) * 1e-9
        if stack:
            stack[-1][1] += ev.dur

    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and ev.start >= stack[-1][0].end:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    lo, hi = trace.window
    evs = [e for d in trace.devices for e in d.ops if e.start >= lo and e.end <= hi]
    st = self_times(evs)
    n = len(trace.devices)
    return [[name, t / n] for name, t in sorted(st.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """Idle time of chip 0 in the window by what the host was doing: each
    stretch in which no op ran is named by the ``bench.*`` span that covers
    its middle, and the stretches are summed per name, largest
    first."""
    lo, hi = trace.window
    busy = busy_intervals(trace.devices[0].ops, lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # the benchmark's spans follow one another on one thread: the last one that
    # starts before a point is the only one that can cover it
    inner = [s for s in trace.spans if s.name != WINDOW_SPAN]
    starts = [s.start for s in inner]
    total: dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        label = inner[i].name if i >= 0 and inner[i].end >= mid else "outside bench spans"
        total[label] = total.get(label, 0.0) + (b - a) * 1e-9
    return [[name, sec] for name, sec in sorted(total.items(), key=lambda kv: -kv[1])[:k]]
