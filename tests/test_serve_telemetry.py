"""Serving telemetry (ISSUE 8): the metrics registry, the structured event
tracer, the engine's phase-timing breakdown, and — decisively — proof that
telemetry never changes a computed value: bitwise output parity with
tracing on vs. off across cache families, pool pressure with faults, and
self-speculative decoding.

Also pins the two satellite bug fixes: page-pool occupancy is sampled at
the cycle peak (post-admission, pre-release — short workloads used to read
0.0), and ``summary()`` without an explicit ``wall_s`` measures the real
first-work -> last-work window instead of fabricating a throughput from
summed per-token latencies.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models.zoo import build_model
from repro.serve import (
    FaultPlan,
    MetricsRegistry,
    Request,
    ServeEngine,
    Tracer,
    audit_engine,
    validate_events,
)
from repro.serve.engine import (
    CYCLE_SPAN,
    PHASE_METRICS,
    PHASE_SPANS,
    STAT_COUNTERS,
)
from repro.serve.telemetry import Histogram

BLOCK = 32
#: phases only the sync cycle runs: it pulls the token ids and finite flags
#: its selection program picked on the device
SYNC_ONLY_PHASES = {"logits_pull", "sample"}


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=BLOCK)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def mla_model():
    cfg = smoke_config("deepseek-v3-671b").with_(kv_bits=4, kv_block=BLOCK)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _workload(cfg, n=5, seed=42, lo=34, hi=48, new_lo=10, new_hi=16):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(lo, hi))
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(new_lo, new_hi)),
        ))
    return reqs


def _run(model, params, reqs, **kw):
    engine = ServeEngine(model, params, slots=2, max_seq=128, **kw)
    for r in reqs:
        engine.submit(r)
    engine.run()
    engine.close()  # async runtime: stop the completion thread (sync no-op)
    return engine


# --------------------------------------------------------------------------
# Histogram: log buckets vs the numpy oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_match_numpy_oracle(dist):
    """p50/p90/p99 within one log-bucket width (relative error growth-1,
    ~9%) of numpy's exact percentiles, across nine decades of scale."""
    rng = np.random.default_rng(7)
    if dist == "lognormal":
        xs = rng.lognormal(mean=-6.0, sigma=2.0, size=4000)
    elif dist == "uniform":
        xs = rng.uniform(1e-6, 10.0, size=4000)
    else:
        # 30/70 mode split keeps every tested quantile inside a mode (a
        # quantile landing in the inter-mode gap is ill-posed for any
        # histogram: numpy interpolates across the gap, buckets cannot)
        xs = np.concatenate([
            rng.normal(2e-4, 2e-5, 1200).clip(1e-9),
            rng.normal(5e-2, 5e-3, 2800).clip(1e-9),
        ])
    h = Histogram("t")
    for x in xs:
        h.record(float(x))
    tol = 2 * (h.growth - 1.0)  # one bucket width, either side
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        est = h.percentile(q)
        assert abs(est - exact) <= tol * exact + 1e-12, (dist, q, est, exact)


def test_histogram_extremes_exact_and_empty_safe():
    h = Histogram("t")
    assert h.percentile(50) == 0.0  # empty
    for v in (0.2, 0.5, 0.9):
        h.record(v)
    assert h.percentile(0) == pytest.approx(0.2)
    assert h.percentile(100) == pytest.approx(0.9)
    s = h.summary()
    assert s["count"] == 3
    assert s["min"] == pytest.approx(0.2)
    assert s["max"] == pytest.approx(0.9)
    assert s["mean"] == pytest.approx(1.6 / 3)


def test_histogram_bucket_edges_partition_the_line():
    h = Histogram("t")
    for v in (0.0, 1e-9, h.lo, h.lo * 1.0000001, 0.1, 3.7, 1e4):
        i = h._bucket(v)
        assert v <= h.bucket_edge(i)
        if i > 0:
            assert v > h.bucket_edge(i - 1)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_counter_monotone_and_type_clash():
    m = MetricsRegistry()
    m.inc("a", 3)
    m.inc("a")
    assert m.value("a") == 4
    with pytest.raises(ValueError, match="negative increment"):
        m.inc("a", -1)
    with pytest.raises(ValueError, match="different kind"):
        m.gauge("a")
    with pytest.raises(ValueError, match="different kind"):
        m.histogram("a")


def test_registry_gauge_watermarks():
    m = MetricsRegistry()
    for v in (5, 2, 9, 4):
        m.set_gauge("g", v)
    g = m.gauge("g")
    assert (g.value, g.hi, g.lo) == (4, 9, 2)


def test_registry_snapshot_and_prometheus_exposition():
    m = MetricsRegistry(namespace="ns")
    m.inc("reqs", 2)
    m.set_gauge("occ", 0.5)
    m.observe("lat", 0.01)
    m.observe("lat", 0.02)
    snap = m.snapshot()
    assert snap["counters"]["reqs"] == 2
    assert snap["gauges"]["occ"]["value"] == 0.5
    assert snap["histograms"]["lat"]["count"] == 2
    text = m.to_prometheus()
    assert "# TYPE ns_reqs counter" in text
    assert "ns_reqs 2" in text.splitlines()
    assert "# TYPE ns_occ gauge" in text
    assert "# TYPE ns_lat histogram" in text
    assert 'ns_lat_bucket{le="+Inf"} 2' in text
    assert "ns_lat_count 2" in text
    # cumulative bucket counts are non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("ns_lat_bucket")]
    assert cums == sorted(cums)


# --------------------------------------------------------------------------
# tracer: span discipline + schema validation
# --------------------------------------------------------------------------

def test_tracer_span_discipline():
    t = Tracer()
    t.begin("queue", uid=1)
    with pytest.raises(ValueError, match="begun twice"):
        t.begin("queue", uid=1)
    with pytest.raises(ValueError, match="never begun"):
        t.end("decode", uid=1)
    assert t.end_open(uid=1) == ["queue"]
    assert t.open_spans() == []
    assert validate_events(t.events) == []


def test_validate_events_catches_each_breach_class():
    def evs(*tail):
        return [{"ph": "B", "name": "queue", "cat": "request", "ts_us": 0,
                 "uid": 1, "args": None}, *tail]

    e = {"ph": "E", "name": "queue", "cat": "request", "ts_us": 5, "uid": 1,
         "args": None}
    assert validate_events(evs(e)) == []
    # dangling span
    assert any("never ended" in v for v in validate_events(evs()))
    # end before begin
    bad = dict(e, ts_us=-3)
    assert any("before its begin" in v for v in validate_events(evs(bad)))
    # unknown uid reference
    ghost = {"ph": "i", "name": "cow", "cat": "event", "ts_us": 1, "uid": 9}
    assert any("unknown request uid 9" in v
               for v in validate_events(evs(e, ghost)))
    # rejected is explicitly unspanned
    rej = {"ph": "i", "name": "rejected", "cat": "request", "ts_us": 1,
           "uid": 9}
    assert validate_events(evs(e, rej)) == []
    # non-alternating lifecycle events (B B after the closed queue span)
    b2 = {"ph": "B", "name": "prefill", "cat": "request", "ts_us": 6,
          "uid": 1}
    b3 = {"ph": "B", "name": "decode", "cat": "request", "ts_us": 7,
          "uid": 1}
    assert any("alternate" in v for v in validate_events(evs(e, b2, b3)))
    # timestamp regression within a request's lifecycle stream
    late = {"ph": "B", "name": "prefill", "cat": "request", "ts_us": 2,
            "uid": 1}
    assert any("regress" in v for v in validate_events(evs(e, late)))
    # missing field
    assert any("missing field" in v
               for v in validate_events([{"ph": "i", "name": "x"}]))


def test_tracer_chrome_trace_structure(tmp_path):
    t = Tracer()
    t.begin("queue", uid=3)
    t.end("queue", uid=3)
    t.complete("schedule", t0=t.clock(), dur_s=0.001, cat="engine")
    t.instant("audit", cat="engine", args={"violations": 0})
    ct = t.chrome_trace()
    evs = ct["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"engine", "requests"}
    req_evs = [e for e in evs if e.get("pid") == 1 and e["ph"] != "M"]
    assert req_evs and all(e["tid"] == 3 for e in req_evs)
    assert all("(req 3)" in e["name"] for e in req_evs)
    x = next(e for e in evs if e["ph"] == "X")
    assert x["pid"] == 0 and x["dur"] >= 0
    # file round-trips
    chrome = t.write_chrome(tmp_path / "trace.json")
    assert json.loads(chrome.read_text())["traceEvents"]
    jsonl = t.write_jsonl(tmp_path / "trace.jsonl")
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert lines == t.events


# --------------------------------------------------------------------------
# engine integration: schema-valid traces, phase breakdown, split latencies
# --------------------------------------------------------------------------

@pytest.mark.parametrize("async_runtime", [False, True])
def test_engine_trace_schema_valid_and_lifecycle_complete(small_model,
                                                          async_runtime):
    cfg, model, params = small_model
    reqs = _workload(cfg)
    engine = _run(model, params, reqs, trace=True, audit_every=2,
                  async_runtime=async_runtime)
    errs = validate_events(engine.tracer.events)
    assert errs == [], errs
    # every request walked queue -> prefill -> decode -> done ("prefill" is
    # also an engine phase record, so count only request-cat events)
    req_names = [e["name"] for e in engine.tracer.events
                 if e["cat"] == "request"]
    for span in ("queue", "prefill", "decode"):
        assert req_names.count(span) == 2 * len(reqs), span  # B + E each
    assert req_names.count("done") == len(reqs)
    # per-cycle phase records are present for every phase the runtime runs
    # (the async runtime consumes ids and flags fused into its decode
    # program: no pull, no sample)
    phase_names = {e["name"] for e in engine.tracer.events
                   if e["cat"] == "engine"}
    ran = set(PHASE_METRICS) - (SYNC_ONLY_PHASES if async_runtime else set())
    assert ran <= phase_names
    assert engine.tracer.open_spans() == []
    assert audit_engine(engine).ok


@pytest.mark.parametrize("async_runtime", [False, True])
def test_engine_phase_breakdown_and_host_stall(small_model, async_runtime):
    """Structural invariants only — never wall-clock magnitudes: phases are
    sub-intervals of their cycles (they sum to at most the cycle total,
    whichever runtime attributed them — under the async runtime device_wait
    moves to the consumption boundary), the stall fraction is a fraction,
    and the pull of the selected ids and the sampling are phases of the sync
    cycle alone: once per decode step there, never under the async runtime,
    which consumes the ids and flags its fused decode program returns."""
    cfg, model, params = small_model
    engine = _run(model, params, _workload(cfg, n=3), trace=True,
                  async_runtime=async_runtime)
    s = engine.summary()
    phases = s["phase_s"]
    assert set(PHASE_METRICS) <= set(phases)
    assert phases["cycle"] > 0
    # phases partition the cycle (minus untimed glue)
    assert sum(v for k, v in phases.items() if k != "cycle") \
        <= phases["cycle"] * 1.05
    assert 0.0 <= s["host_stall_fraction"] <= 1.0
    recorded = {name: engine.metrics.hist(h).n
                for name, h in PHASE_METRICS.items()}
    steps = engine.stats["steps"]
    assert steps > 0
    for name in SYNC_ONLY_PHASES:
        assert recorded[name] == (0 if async_runtime else steps), name
        assert (phases[name] == 0.0) == async_runtime, name


def _profiled_run(tmp_path, model, params, reqs):
    """Serve ``reqs`` under the profiler (Python tracer off) and return the
    engine and the ``serve.*`` / ``PjitFunction`` host events of the trace,
    each as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    engine = ServeEngine(model, params, slots=2, max_seq=128)
    for r in reqs:
        engine.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        engine.run()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith(("serve.", "PjitFunction("))
    ]
    return engine, events


def test_engine_phases_are_profiler_spans(tmp_path, small_model):
    """Every cycle and phase is a span on the profiler's clock: the sync
    phases nest in their ``serve.cycle``, the decode program runs inside
    ``serve.decode_dispatch`` under its own name, and the ``serve.prefill``
    stats add up to the registry's prefill counters."""
    cfg, model, params = small_model
    engine, events = _profiled_run(tmp_path, model, params,
                                   _workload(cfg, n=3))

    def named(name):
        return [e for e in events if e[0] == name]

    def inside(ev, spans):
        return any(a <= ev[1] and ev[2] <= b for _, a, b, _ in spans)

    cycles = named(CYCLE_SPAN)
    assert len(cycles) == engine._cycle
    assert sorted(c[3]["cycle"] for c in cycles) == list(
        range(1, engine._cycle + 1))
    for name, span in PHASE_SPANS.items():
        got = named(span)
        assert got, name
        assert all(inside(e, cycles) for e in got), name
    assert len(named("serve.logits_pull")) == engine.stats["steps"]
    dispatch = named("serve.decode_dispatch")
    programs = named("PjitFunction(serve_decode_step)")
    assert len(dispatch) == engine.stats["steps"]
    assert all(inside(e, dispatch) for e in programs)
    assert all(any(d[1] <= p[1] and p[2] <= d[2] for p in programs)
               for d in dispatch)
    assert not any("lambda" in e[0] for e in events)
    prefills = named("serve.prefill")
    assert len(prefills) == engine.stats["prefill_calls"]
    assert sum(e[3]["tokens"] for e in prefills) \
        == engine.stats["prefill_tokens"] > 0
    assert sum(e[3]["computed"] for e in prefills) \
        == engine.stats["prefill_tokens_computed"] \
        > engine.stats["prefill_tokens"]


def test_logits_pull_fetches_only_ids_and_flags(tmp_path, small_model):
    """The sync cycle picks the greedy token on the device
    (``serve_select_tokens``, dispatched inside ``serve.decode_dispatch``
    behind the decode program) and pulls ``[slots]`` int32 ids and bool
    finite flags: 5 bytes a slot per decode step, counted by
    ``decode_pull_bytes`` and carried as the ``bytes`` stat of every
    ``serve.logits_pull`` span."""
    cfg, model, params = small_model
    engine, events = _profiled_run(tmp_path, model, params,
                                   _workload(cfg, n=3))
    per_step = engine.slots * 5
    steps = engine.stats["steps"]
    assert steps > 0
    assert engine.stats["decode_pull_bytes"] == steps * per_step
    pulls = [e for e in events if e[0] == "serve.logits_pull"]
    assert [e[3]["bytes"] for e in pulls] == [per_step] * steps
    dispatch = [e for e in events if e[0] == "serve.decode_dispatch"]
    selects = [e for e in events
               if e[0] == "PjitFunction(serve_select_tokens)"]
    assert len(dispatch) == steps
    assert all(any(d[1] <= p[1] and p[2] <= d[2] for d in dispatch)
               for p in selects)
    assert all(any(d[1] <= p[1] and p[2] <= d[2] for p in selects)
               for d in dispatch)


def test_moe_counters_ride_the_decode_pull(tmp_path, small_model, mla_model):
    """A model with routed experts counts, per decode step, the (token,
    expert) pairs whose expert it holds and the held experts hit, summed over
    its MoE layers: ``moe_pairs_here`` and ``moe_experts_hit`` in ``stats``
    and as stats of every decode cycle's ``serve.advance`` span, pulled with
    the ids and flags (8 B more a step).  A model without experts has
    neither, and its pull stays at 5 B a slot."""
    from repro.serve.engine import MOE_COUNTERS

    cfg, model, params = mla_model
    engine, events = _profiled_run(tmp_path / "mla", model, params,
                                   _workload(cfg, n=3))
    steps = engine.stats["steps"]
    advance = [e[3] for e in events if e[0] == "serve.advance"
               and "moe_pairs_here" in e[3]]
    assert len(advance) == steps > 0
    for name in MOE_COUNTERS:
        assert engine.stats[name] == sum(a[name] for a in advance) > 0
    # every slot's token routes top_k pairs in each MoE layer, all held here
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert all(a["moe_pairs_here"] == engine.slots * cfg.top_k * n_moe
               for a in advance)
    assert all(1 <= a["moe_experts_hit"] <= cfg.held_experts * n_moe
               for a in advance)
    assert engine.stats["decode_pull_bytes"] == steps * (engine.slots * 5 + 8)

    cfg, model, params = small_model
    engine, events = _profiled_run(tmp_path / "dense", model, params,
                                   _workload(cfg, n=2))
    assert not set(MOE_COUNTERS) & set(engine.stats)
    assert not any(set(MOE_COUNTERS) & set(e[3]) for e in events)
    assert engine.stats["decode_pull_bytes"] == engine.stats["steps"] * engine.slots * 5


def test_ttft_tpot_split_latency_series(small_model):
    cfg, model, params = small_model
    reqs = _workload(cfg, n=4)
    engine = _run(model, params, reqs)
    # one TTFT sample per completed request; everything else is TPOT
    assert engine.metrics.hist("ttft_s").n == len(reqs)
    decoded = engine.stats["decoded_tokens"]
    assert engine.metrics.hist("tpot_s").n == decoded - len(reqs)
    assert engine.metrics.hist("queue_wait_s").n == len(reqs)
    assert engine.metrics.hist("e2e_latency_s").n == len(reqs)
    s = engine.summary()
    # structural only — the split exists and both series carry real samples;
    # comparing TTFT/TPOT *magnitudes* is a wall-clock race (scheduler noise
    # or the async runtime's pipelined latencies can flip either way)
    assert s["ttft_p50_ms"] > 0.0
    assert s["tpot_p50_ms"] > 0.0
    assert s["e2e_p99_ms"] > 0.0


def test_stats_property_remains_dict_compatible(small_model):
    cfg, model, params = small_model
    engine = _run(model, params, _workload(cfg, n=2))
    stats = engine.stats
    assert set(stats) == set(STAT_COUNTERS)
    assert all(isinstance(v, int) for v in stats.values())
    assert stats["decoded_tokens"] > 0
    assert stats["budget_retired"] == 2


# --------------------------------------------------------------------------
# satellite fixes: occupancy sampling + the wall_s work window
# --------------------------------------------------------------------------

def test_occupancy_sampled_at_cycle_peak_not_after_release(small_model):
    """Regression: occupancy was sampled after ``_advance`` released the
    retiring requests' pages, so a workload whose requests all retire
    within a few cycles of first allocating reported 0.0 forever."""
    cfg, model, params = small_model
    # prompts just over one block, one decoded token: pages live briefly
    reqs = _workload(cfg, n=2, lo=BLOCK + 2, hi=BLOCK + 6,
                     new_lo=1, new_hi=2)
    engine = _run(model, params, reqs)
    s = engine.summary()
    assert s["occupancy_max"] > 0.0
    assert s["occupancy_mean"] > 0.0
    # the gauge high-water mark agrees with the sampled series
    assert engine.metrics.gauge("pool_occupancy").hi >= s["occupancy_max"]


def test_pool_gauges_track_usage_and_drain(small_model):
    cfg, model, params = small_model
    engine = _run(model, params, _workload(cfg, n=3))
    used = engine.metrics.gauge("pool_pages_used")
    assert used.hi > 0       # pages were allocated at some point
    assert used.value == 0   # and all returned at drain
    assert engine.metrics.gauge("pool_pages_committed").value == 0


def test_wall_s_measures_work_window_not_fabricated(small_model):
    """Regression: ``summary()`` without wall_s derived throughput from
    summed per-token latencies / slots — a fabrication once TTFT includes
    queue wait.  Now it reports the first-work -> last-work window."""
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_seq=128)
    s0 = engine.summary()
    assert s0["wall_s"] == 0.0 and s0["tokens_per_s"] == 0.0  # no work yet
    for r in _workload(cfg, n=2):
        engine.submit(r)
    while engine._has_work():
        engine.step()
    s = engine.summary()
    assert s["wall_s"] > 0.0
    assert s["decoded_tokens"] / s["wall_s"] == pytest.approx(
        s["tokens_per_s"])
    # an explicit wall time still wins
    assert engine.summary(wall_s=100.0)["tokens_per_s"] == pytest.approx(
        s["decoded_tokens"] / 100.0)


# --------------------------------------------------------------------------
# metrics sink + fault observer
# --------------------------------------------------------------------------

def test_metrics_every_feeds_sink_each_n_cycles(small_model):
    cfg, model, params = small_model
    seen = []
    engine = _run(model, params, _workload(cfg, n=2),
                  metrics_every=2, metrics_sink=seen.append)
    assert len(seen) == engine._cycle // 2
    assert all("counters" in snap for snap in seen)
    # snapshots are monotone in decoded tokens
    tok = [snap["counters"]["decoded_tokens"] for snap in seen]
    assert tok == sorted(tok)


def test_fault_firings_count_and_trace(small_model):
    cfg, model, params = small_model
    plan = FaultPlan(seed=3, fire_at={"forced_preempt": (4,)})
    reqs = _workload(cfg)
    engine = _run(model, params, reqs, trace=True, faults=plan,
                  n_pages=2 + 6, reserve_policy="expected",
                  expected_quantile=0.25, audit_every=1)
    assert engine.stats["faults_injected"] == len(plan.log) == 1
    faults = [e for e in engine.tracer.events if e["name"] == "fault"]
    assert [f["args"]["site"] for f in faults] == ["forced_preempt"]
    # the preemption shows in the trace too: preempt instant + re-queue
    names = [e["name"] for e in engine.tracer.events]
    assert "preempt" in names
    assert validate_events(engine.tracer.events) == []
    assert all(r.done for r in reqs)


# --------------------------------------------------------------------------
# the decisive bar: telemetry never changes a computed value
# --------------------------------------------------------------------------

def _outputs(engine_reqs):
    return {r.uid: list(r.out_tokens) for r in engine_reqs}


@pytest.mark.parametrize("family", ["attn", "mla"])
def test_tracing_is_bitwise_invisible_per_family(
        family, small_model, mla_model):
    cfg, model, params = small_model if family == "attn" else mla_model
    base = _workload(cfg)
    traced = _workload(cfg)
    _run(model, params, base)
    engine = _run(model, params, traced, trace=True, audit_every=2,
                  metrics_every=3, metrics_sink=lambda snap: None)
    assert _outputs(traced) == _outputs(base)
    assert validate_events(engine.tracer.events) == []


def test_tracing_is_bitwise_invisible_under_pressure(small_model):
    cfg, model, params = small_model
    kw = dict(n_pages=2 + 3, reserve_policy="expected",
              expected_quantile=0.0, audit_every=1)
    base = _workload(cfg, new_lo=24, new_hi=32)
    traced = _workload(cfg, new_lo=24, new_hi=32)
    ref = _run(model, params, base, **kw)
    engine = _run(model, params, traced, trace=True, **kw)
    assert ref.stats["preempted"] > 0  # pressure actually happened
    assert engine.stats["preempted"] == ref.stats["preempted"]
    assert _outputs(traced) == _outputs(base)
    assert validate_events(engine.tracer.events) == []


def test_tracing_is_bitwise_invisible_with_speculation(small_model):
    cfg, model, params = small_model
    base = _workload(cfg)
    traced = _workload(cfg)
    ref = _run(model, params, base, spec_k=2, spec_bits=2)
    engine = _run(model, params, traced, spec_k=2, spec_bits=2, trace=True,
                  audit_every=2)
    assert engine.stats["spec_draft_tokens"] == ref.stats["spec_draft_tokens"]
    assert _outputs(traced) == _outputs(base)
    errs = validate_events(engine.tracer.events)
    assert errs == [], errs
    names = {e["name"] for e in engine.tracer.events}
    assert {"spec_draft", "spec_verify"} <= names
