"""The trace reduction, on a trace recorded on a TPU v5e: 12 decode steps
of a 2-layer model at Qwen2-7B widths (8 slots), driven under
``bench.step`` spans, one ``bench.submit`` among them."""
import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).parent / "data" / "decode_steps.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA)


def test_devices_and_spans(trace):
    assert len(trace.devices) == 1
    assert {s.name for s in trace.spans} == {"bench.step", "bench.submit"}
    assert 0.09 < trace.window_s < 0.11


def test_programs_and_kernels(trace):
    assert len(tr.programs_with(trace, "paged_bitdecode")) == 12
    assert len(tr.kernel_events(trace, "paged_bitdecode")) == 24  # 2 layers x 12 steps
    # the flush runs only in steps where some row's residual block fills
    assert len(tr.kernel_events(trace, "paged_residual_flush")) == 6
    assert tr.programs_with(trace, "kv_quant") == []
    ms = 1e-6 * sum(p.dur for p in tr.programs_with(trace, "paged_bitdecode")) / 12
    assert 2.8 < ms < 3.1


def test_busy_is_the_union_and_self_times_partition_it(trace):
    busy = tr.busy_s(trace)
    assert 0 < busy < trace.window_s
    lo, hi = trace.window
    ops = [e for e in trace.devices[0].ops if e.start >= lo and e.end <= hi]
    assert sum(tr.self_times(ops).values()) == pytest.approx(busy, rel=1e-6)
    assert sum(t for _, t in tr.top_ops(trace, k=10_000)) == pytest.approx(busy, rel=1e-6)


def test_idle_gaps_are_named_by_the_host_span(trace):
    gaps = tr.idle_gaps(trace)
    assert [name for name, _ in gaps] == ["bench.step"]
    assert gaps[0][1] == pytest.approx(trace.window_s - tr.busy_s(trace), rel=1e-6)


def test_self_times_of_nested_events():
    ev = tr.Event
    evs = [ev("%while", 0, 100), ev("%a", 10, 20), ev("%b", 40, 30), ev("%c", 120, 5)]
    st = tr.self_times(evs)
    assert st == pytest.approx({"%while": 50e-9, "%a": 20e-9, "%b": 30e-9, "%c": 5e-9})


def test_busy_intervals_merge_overlaps():
    ev = tr.Event
    got = tr.busy_intervals([ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 40, 0)], 2, 33)
    assert got == [(2, 15), (30, 33)]
