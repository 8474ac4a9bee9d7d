"""The serving engine's own profiler spans in a traced run, and the
device's idle time charged to them.

The engine (``repro/serve/engine.py``) opens a ``serve.cycle`` span around
each cycle and a ``serve.<phase>`` span around each phase block
(``schedule``, ``prefill``, ``decode_dispatch``, ``device_wait``,
``logits_pull``, ``sample``, ``advance``); ``serve.prefill`` carries the
stats ``tokens`` (the admitted rows' suffix tokens) and ``computed`` (the
tokens the prefill program computed).  They are host events of the same
trace, on the same clock, as the device's operations.

For a run's trace (``RunData.trace``, which keeps only the ``bench.*``
spans) :func:`for_run` finds the ``.xplane.pb`` under
``bench_out/trace-*/`` whose ``bench.window`` span is the run's window,
reads its ``serve.*`` spans once per file, and gives the readers in
``bench/metrics/``: the spans inside the window, the cycles with the spans
nested in each, and the idle time of chip 0 charged to the innermost span
that covers each stretch of it (``idle_by_span``).  A trace of a program
without these spans gives no cycles, and every reader of them returns
None.

    python3 bench/spans.py [bench_out]

prints, for every trace under ``<dir>/trace-*/``, the idle time by span
and the four span metrics.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402

TRACE_ROOT = ROOT / "bench_out"
PREFIX = "serve."
CYCLE = "serve.cycle"
OUTSIDE = "outside spans"
METRICS = ("step_host_ms", "logits_pull_ms", "sample_ms", "prefill_token_use")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Cycle:
    span: Span
    inner: list[Span]   # the serve.* spans inside it

    def time(self, name: str) -> float:
        """Nanoseconds of the inner spans named ``name``."""
        return sum(s.dur for s in self.inner if s.name == name)

    def has(self, name: str) -> bool:
        return any(s.name == name for s in self.inner)


@dataclasses.dataclass
class Spans:
    spans: list[Span]          # serve.* spans inside the window, by start
    cycles: list[Cycle]
    idle_by_span: list[list]   # [name, idle seconds of chip 0], largest first

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def decode_cycles(self) -> list[Cycle]:
        """Cycles that dispatched a decode step."""
        return [c for c in self.cycles if c.has("serve.decode_dispatch")]


def read_host(path) -> tuple[tuple[float, float] | None, list[Span]]:
    """The ``bench.window`` interval (None where there is none) and every
    ``serve.*`` host span, with its stats, of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window, spans = None, []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    spans.append(Span(name, float(e.start_ns), float(e.duration_ns),
                                      dict(e.stats)))
                elif name == tr.WINDOW_SPAN and window is None:
                    # the same arithmetic as trace.load, so the windows compare equal
                    start = float(e.start_ns)
                    window = (start, start + float(e.duration_ns))
    return window, spans


# per trace file (path, mtime, size): what it holds, and its Spans once built
_HOST: dict[tuple, tuple] = {}
_BUILT: dict[tuple, Spans] = {}


def _host(path: pathlib.Path) -> tuple:
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _HOST:
        _HOST[key] = read_host(path)
    return key, _HOST[key]


def trace_files() -> list[pathlib.Path]:
    return sorted(TRACE_ROOT.glob("trace-*/**/*.xplane.pb"))


def for_run(run) -> Spans | None:
    """The engine spans of the trace behind ``run.trace``: None without a
    trace, or where no trace file holds the run's window."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None
    window = tuple(trace.window)
    for path in trace_files():
        key, (w, spans) = _host(path)
        if w == window:
            if key not in _BUILT:
                _BUILT[key] = build(trace, spans)
            return _BUILT[key]
    return None


def build(trace: tr.Trace, spans: list[Span]) -> Spans:
    """The spans inside ``trace``'s window, nested into cycles, and chip 0's
    idle time charged to them."""
    lo, hi = trace.window
    inside = sorted((s for s in spans if s.start >= lo and s.end <= hi),
                    key=lambda s: (s.start, -s.dur))
    starts = [s.start for s in inside]
    cycles = []
    for c in inside:
        if c.name != CYCLE:
            continue
        i = bisect.bisect_left(starts, c.start)
        inner = []
        while i < len(inside) and inside[i].start < c.end:
            s = inside[i]
            if s is not c and s.name != CYCLE and s.end <= c.end:
                inner.append(s)
            i += 1
        cycles.append(Cycle(c, inner))
    return Spans(inside, cycles, idle_by_span(trace, inside))


def segments(spans: list, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi] cut into stretches, each named by the innermost of the
    (nested) ``spans`` that covers it, or ``OUTSIDE``."""
    out: list[tuple[float, float, str]] = []
    stack: list = []
    t = lo

    def upto(until: float) -> None:
        nonlocal t
        until = min(until, hi)
        if until > t:
            out.append((t, until, stack[-1].name if stack else OUTSIDE))
            t = until

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1].end <= s.start:
            upto(stack[-1].end)
            stack.pop()
        upto(s.start)
        stack.append(s)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def idle_by_span(trace: tr.Trace, spans: list[Span]) -> list[list]:
    """Seconds in which no op ran on chip 0, by the innermost span the host
    was in: a ``serve.*`` span, else a ``bench.*`` span of the harness,
    else ``OUTSIDE``.  A gap that crosses span boundaries is split at them.
    Empty without a device."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in tr.busy_intervals(trace.devices[0].ops, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    harness = [s for s in trace.spans if s.name != tr.WINDOW_SPAN]
    segs = segments(list(spans) + harness, lo, hi)
    seg_starts = [a for a, _, _ in segs]
    total: dict[str, float] = {}
    for a, b in gaps:
        i = max(0, bisect.bisect_right(seg_starts, a) - 1)
        while i < len(segs) and segs[i][0] < b:
            sa, sb, name = segs[i]
            cut = min(b, sb) - max(a, sa)
            if cut > 0:
                total[name] = total.get(name, 0.0) + cut * 1e-9
            i += 1
    return [[name, sec] for name, sec in sorted(total.items(), key=lambda kv: -kv[1])]


def main(argv=None) -> int:
    import importlib.util
    import types

    from bench import spans  # the module the readers import, not __main__

    argv = sys.argv[1:] if argv is None else argv
    if argv:
        spans.TRACE_ROOT = pathlib.Path(argv[0])
    for path in spans.trace_files():
        trace = tr.load(path)
        run = types.SimpleNamespace(trace=trace)
        sp = spans.for_run(run)
        idle = sum(sec for _, sec in sp.idle_by_span)
        print(f"{path}: window {trace.window_s:.3f} s, idle {idle:.3f} s, "
              f"{len(sp.decode_cycles())} decode cycles, "
              f"{len(sp.named('serve.prefill'))} prefills")
        for name, sec in sp.idle_by_span:
            print(f"  idle {name:24s} {sec:10.4f} s  {100 * sec / max(idle, 1e-12):6.2f} %")
        named = sum(sec for name, sec in sp.idle_by_span if name.startswith(PREFIX))
        print(f"  idle under serve.* spans: {100 * named / max(idle, 1e-12):.2f} %")
        for name in METRICS:
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            print(f"  {name} {mod.read(run)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
