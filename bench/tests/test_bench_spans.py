"""The engine's profiler spans as ``bench/spans.py`` and its four readers
see them: on a trace recorded on a TPU v5e (``record_serve_spans.py``: a
2-layer model at Qwen2-7B widths, 8 slots, one 200-token prompt admitted
into a 256-token bucket and 6 decode steps), on a trace without the
engine's spans (the earlier ``decode_steps.xplane.pb``), and on a traced
run of the tiny harness on the CPU."""
import pathlib
import shutil

import pytest

from bench import run, spans, trace as tr
from bench.tests import tiny

DATA = pathlib.Path(__file__).parent / "data"
SERVE = DATA / "serve_spans.xplane.pb"
SYNC_PHASES = ("serve.schedule", "serve.decode_dispatch", "serve.device_wait",
               "serve.logits_pull", "serve.sample", "serve.advance")


def _metrics(names):
    return [{"name": n, "unit": ""} for n in names]


def _run_data(trace, steps=()):
    return run.RunData(tiny.tiny_config("qwen2-7b"), None, trace, [], list(steps), 0.0, 1.0)


@pytest.fixture
def staged(tmp_path, monkeypatch):
    """Puts a trace file where ``spans.for_run`` looks for it and returns
    the run data of that trace."""
    def stage(src):
        dst = tmp_path / "trace-cell" / src.name
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
        return _run_data(tr.load(dst))

    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    return stage


@pytest.fixture(scope="module")
def chip():
    trace = tr.load(SERVE)
    return trace, spans.build(trace, spans.read_host(SERVE)[1])


def test_trace_is_small_and_names_its_programs(chip):
    trace, _ = chip
    assert SERVE.stat().st_size < 2 * 2**20
    names = {m.name.split("(")[0] for m in trace.devices[0].modules}
    assert {"jit_serve_decode_step", "jit_serve_prefill"} <= names
    assert "jit__lambda" not in names
    progs = tr.programs_with(trace, "paged_bitdecode")
    assert len(progs) == 6
    assert {p.name.split("(")[0] for p in progs} == {"jit_serve_decode_step"}


def test_every_phase_nests_in_its_cycle(chip):
    _, sp = chip
    assert len(sp.cycles) == len(sp.decode_cycles()) == 6
    for c in sp.cycles:
        for name in SYNC_PHASES:
            assert c.has(name), name
        assert c.span.start <= min(s.start for s in c.inner)
        assert max(s.end for s in c.inner) <= c.span.end
    nested = sum(len(c.inner) for c in sp.cycles)
    assert nested + len(sp.cycles) == len(sp.spans)
    (pre,) = sp.named("serve.prefill")
    assert pre.stats == {"tokens": 200, "computed": 8 * 256}
    assert sp.cycles[0].has("serve.prefill")


def test_idle_by_span_partitions_the_idle_time(chip):
    trace, sp = chip
    idle = sum(sec for _, sec in sp.idle_by_span)
    assert idle == pytest.approx(trace.window_s - tr.busy_s(trace), rel=1e-6)
    named = sum(sec for name, sec in sp.idle_by_span if name.startswith("serve."))
    assert named >= 0.9 * idle
    top = {name for name, _ in sp.idle_by_span[:3]}
    assert top & {"serve.logits_pull", "serve.sample"}


def test_span_readers_on_the_chip_trace(staged):
    rd = staged(SERVE)
    got = {k: v["value"] for k, v in run.read_metrics(_metrics(spans.METRICS), rd).items()}
    assert set(got) == set(spans.METRICS)
    sp = spans.for_run(rd)
    cycles = sp.decode_cycles()
    pull = sum(s.dur for s in sp.named("serve.logits_pull")) / len(cycles)
    assert got["logits_pull_ms"] == pytest.approx(1e-6 * pull)
    assert 0 < got["logits_pull_ms"] + got["sample_ms"] < got["step_host_ms"]
    assert got["prefill_token_use"] == pytest.approx(100 * 200 / 2048)


def test_span_readers_find_nothing_without_engine_spans(monkeypatch):
    """A program without the engine's spans (the trace holds only the
    harness's): no cycles, the idle time under the harness's spans, and
    every reader leaves its metric out."""
    path = DATA / "decode_steps.xplane.pb"
    trace = tr.load(path)
    sp = spans.build(trace, spans.read_host(path)[1])
    assert sp.cycles == [] and sp.spans == []
    idle = dict(sp.idle_by_span)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s - tr.busy_s(trace), rel=1e-6)
    assert sp.idle_by_span[0][0] == "bench.step"
    assert idle["bench.step"] > 0.99 * sum(idle.values())
    monkeypatch.setattr(spans, "for_run", lambda run: sp)
    assert run.read_metrics(_metrics(spans.METRICS), _run_data(trace)) == {}


def test_span_readers_find_nothing_without_a_matching_file(staged, tmp_path):
    rd = staged(SERVE)
    rd.trace.window = (rd.trace.window[0] + 1.0, rd.trace.window[1])
    assert spans.for_run(rd) is None
    assert run.read_metrics(_metrics(spans.METRICS), rd) == {}


def test_segments_name_the_innermost_span():
    S = spans.Span
    outer = S("serve.cycle", 10, 90, {})
    a = S("serve.device_wait", 20, 30, {})
    b = S("serve.logits_pull", 50, 10, {})
    harness = tr.Event("bench.client", 105, 5)
    got = spans.segments([outer, a, b, harness], 0, 120)
    assert got == [(0, 10, spans.OUTSIDE), (10, 20, "serve.cycle"),
                   (20, 50, "serve.device_wait"), (50, 60, "serve.logits_pull"),
                   (60, 100, "serve.cycle"), (100, 105, spans.OUTSIDE),
                   (105, 110, "bench.client"), (110, 120, spans.OUTSIDE)]


def test_span_readers_on_the_tiny_harness(tmp_path, monkeypatch):
    """A traced window of the tiny engine driven as ``run.py`` drives it:
    the readers agree with the engine's own counters over the window."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import model, traffic as tf

    conf, mix = tiny.tiny_config("qwen2-7b"), tiny.tiny_mix("reason")
    weights = model.reference_module(conf).init_weights(conf, model.jax_key(5))
    engine = model.build_engine(conf, model.build_program(conf), weights)
    drv = run.Driver(engine, tf.Traffic(mix, 5, conf["vocab_size"]))
    for p, b in drv.traffic.first_wave():
        drv.send(p, b)
    for _ in range(int(mix["warm_decode_steps"])):
        drv.step()
    before = engine.stats
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace-tiny"), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(60):  # past the largest output: every client finishes
            drv.step()
    jax.profiler.stop_trace()
    after = engine.stats
    delta = {k: after[k] - before[k] for k in after}

    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    rd = _run_data(tr.load(tr.find_trace_file(tmp_path / "trace-tiny")),
                   drv.steps[-60:])
    got = {k: v["value"] for k, v in run.read_metrics(_metrics(spans.METRICS), rd).items()}
    sp = spans.for_run(rd)
    assert len(sp.decode_cycles()) == delta["steps"] == 60
    assert len(sp.named("serve.prefill")) == delta["prefill_calls"] > 0
    assert got["prefill_token_use"] == pytest.approx(
        100 * delta["prefill_tokens"] / delta["prefill_tokens_computed"])
    assert 0 < got["logits_pull_ms"] + got["sample_ms"] < got["step_host_ms"]
    assert sp.idle_by_span == []  # no device plane on the CPU
