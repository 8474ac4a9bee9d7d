"""Benchmark of the serving engine on the chip, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  One run:

1. set-up: makes the weights from the seed on the device in one jitted
   call, builds the engine, warms every program and eager shape the cell's
   traffic can reach, submits each client's first request and runs the
   mix's ``warm_decode_steps``;
2. window: drives ``ServeEngine.step()`` for ``--seconds`` seconds (with
   ``--trace 1``, for at most ``TRACE_S`` seconds under the profiler),
   timing every request from when its client sent it and every token from
   the ``step()`` return that made it visible; counts compiles in the
   window;
3. reads the device's peak memory, frees the engine's state, and compares
   a seeded sample of finished requests with the plain reference
   (``bench/check.py``);
4. prints the checks on standard error and, as the last line of standard
   output, one JSON object: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
   per-layer metrics, each read by ``bench/metrics/<name>.py``; ``setup_s``
   is the harness's own), ``device``, ``breakdown`` (traced runs) and
   ``checks``.

It needs a TPU with the cell's chips: without one it exits 2 and prints no
result.  The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_S = 10.0          # longest traced window
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"  # a compile or a cache load
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SPLICE_BYTES = 2e9      # device memory the warm-up's concurrent splices may add


def log(*a):
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Client:
    """What the client side saw of one request."""
    req: object
    due: float
    submitted: float | None = None
    token_times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    """One ``ServeEngine.step()`` as the client saw it."""
    t: float            # its return
    tokens: int         # tokens it made visible
    contexts: list      # context of each row that got a token


@dataclasses.dataclass
class RunData:
    """Everything a metric reader (``bench/metrics``) may read: the
    window's steps and clients, and with ``--trace 1`` the trace."""
    conf: dict
    peaks: dict
    trace: object
    clients: list
    steps: list
    t0: float
    t1: float

    @property
    def decode_contexts(self):
        return [s.contexts for s in self.steps]

    def programs_with(self, kernel):
        from bench import trace as tr
        return tr.programs_with(self.trace, kernel) if self.trace else []

    def kernel_events(self, kernel):
        from bench import trace as tr
        return tr.kernel_events(self.trace, kernel) if self.trace else []


class Driver:
    """Feeds one engine from a closed loop of clients and records what they
    see: a client sends its next request when its last one finished."""

    def __init__(self, engine, traffic):
        self.engine = engine
        self.traffic = traffic
        self.clients: list[Client] = []
        self.waiting: collections.deque = collections.deque()
        self.inflight: list[Client] = []
        self.steps: list[Step] = []
        self.uid = 0

    def send(self, prompt_len, budget):
        from repro.serve.engine import Request

        req = Request(uid=self.uid, prompt=self.traffic.tokens(prompt_len),
                      max_new_tokens=budget)
        self.uid += 1
        c = Client(req, time.perf_counter())
        self.clients.append(c)
        self.waiting.append(c)

    def step(self):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.submit"):
            while self.waiting:
                c = self.waiting.popleft()
                c.submitted = time.perf_counter()
                self.engine.submit(c.req)
                self.inflight.append(c)
        before = [len(c.req.out_tokens) for c in self.inflight]
        with TraceAnnotation("bench.step"):
            self.engine.step()
        t = time.perf_counter()
        with TraceAnnotation("bench.client"):
            ctx, still, made = [], [], 0
            for c, n in zip(self.inflight, before):
                new = len(c.req.out_tokens) - n
                c.token_times.extend([t] * new)
                made += new
                if new:
                    ctx.append(c.req.pos)
                if c.req.finished:
                    self.send(*self.traffic.next_sizes())
                else:
                    still.append(c)
            self.inflight = still
            self.steps.append(Step(t, made, ctx))
        return t


# ------------------------------------------------------------------ warm-up


def warm_shapes(engine, traffic, slots):
    """Compile (or load from the persistent cache) every program and eager
    shape the mix reaches: the decode step, the prefill of each bucket a
    prompt can fall in, and the splice of a prefill into the pool
    (``pages.adopt_prefill``, whose eager ops compile once per count of
    admitted rows and per count of complete prompt blocks) for up to the
    mix's ``warm_admissions`` requests admitted together.  The splices
    compile on as many threads as ``SPLICE_BYTES`` of spare pool copies
    allow: each shape is a separate small program.  The first wave's
    larger admissions have the same sizes in every run and come from the
    cache after a checkout's first run."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import pages as pg
    from repro.serve.scheduler import bucket_for

    block = engine.block_n
    jax.block_until_ready(engine._step(engine.params, engine.state,
                                       jnp.asarray(engine.tokens))[0])
    log(f"[setup] decode step at {time.perf_counter() - T_PROCESS:.1f}s")
    buckets = sorted({bucket_for(p, min_bucket=engine.sched.min_bucket)
                      for p in traffic.prompts})
    dense = None
    for b in buckets:
        logits, st = engine._prefill(engine.params, jnp.zeros((slots, b), jnp.int32),
                                     jnp.ones((slots,), jnp.int32))
        np.asarray(logits)
        dense = st["caches"]
        del logits, st
    log(f"[setup] prefill buckets {buckets} at {time.perf_counter() - T_PROCESS:.1f}s")
    nb_dense = dense[0].kw.shape[3]
    m_max = min(slots, int(traffic.mix["warm_admissions"]))
    n_max = m_max * (traffic.max_prompt // block)

    def splice(m, n):
        per = [[] for _ in range(m)]
        for j in range(n):
            per[j % m].append(slots + j)
        if any(len(p) > nb_dense for p in per):
            raise ValueError("warm-up spreads more blocks over a row than it holds")
        out = pg.adopt_prefill(engine.state["caches"], dense, slot_ids=list(range(m)),
                               lengths=[len(p) * block for p in per],
                               pages_per_req=per, block_n=block)
        pos = engine.state["pos"].at[jnp.asarray(list(range(m)), jnp.int32)].set(
            jnp.asarray([1] * m, jnp.int32))
        jax.block_until_ready((out, pos))

    shapes = [(min(m_max, max(1, n)), n) for n in range(n_max, -1, -1)]
    shapes += [(m, 0) for m in range(1, m_max + 1)]
    # each splice holds a second copy of the pools while it runs
    workers = max(1, min(8, int(SPLICE_BYTES // (engine.n_pages * engine.kv_page_bytes))))
    with concurrent.futures.ThreadPoolExecutor(max_workers=workers) as pool:
        for f in [pool.submit(splice, m, n) for m, n in shapes]:
            f.result()
    log(f"[setup] {len(shapes)} splice shapes (rows <= {m_max}, blocks <= {n_max}) "
        f"at {time.perf_counter() - T_PROCESS:.1f}s")
    del dense
    gc.collect()


# ------------------------------------------------------------------ metrics


def read_metrics(cell_metrics, run):
    """Each metric's value from its reader ``bench/metrics/<name>.py``;
    a reader that finds nothing to read returns None and the metric is
    left out.  ``setup_s`` is measured by the harness."""
    out = {}
    for m in cell_metrics:
        if m["name"] == "setup_s":
            continue
        path = ROOT / "bench" / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ main


def cell_metrics(bench, cell, kind):
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    use_compile_cache()

    from bench import model, traffic as tf

    out = run_cell(bench, cell, model.load_config(cell["config"]),
                   tf.load_mix(cell["traffic"]), load_limits(cell["name"]), args)
    print(json.dumps(out), flush=True)
    return 0


def use_compile_cache():
    """Keep every compiled program in the persistent cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
    checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def load_limits(cell_name: str) -> dict:
    return json.loads((ROOT / "bench" / "cells" / f"{cell_name}.json").read_text())


def run_cell(bench, cell, conf, mix, limits, args, *, engine_hook=None, control=False):
    """One run of ``cell``; returns the result object.  ``engine_hook``, if
    given, receives the engine before set-up (tests break the timed path
    through it).  With ``control`` the fp8 control (``bench/check.py``)
    takes the served tokens' place in the comparison, which benchmark runs
    never do: its first choices are judged by the same limit."""
    import jax

    dev = jax.devices()[0]
    events = collections.Counter()

    def on_event(event, *a, **kw):
        events[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_event)

    from bench import check, model, peaks as pk, traffic as tf
    from bench import trace as tr

    ref = model.reference_module(conf)
    program = model.build_program(conf)
    weights = ref.init_weights(conf, model.jax_key(args.seed))
    jax.block_until_ready(weights)
    log(f"[setup] weights at {time.perf_counter() - T_PROCESS:.1f}s")
    model.check_tree(program, weights)
    engine = model.build_engine(conf, program, weights)
    if engine_hook is not None:
        engine_hook(engine)
    traffic = tf.Traffic(mix, args.seed, conf["vocab_size"])
    warm_shapes(engine, traffic, conf["engine"]["slots"])
    drv = Driver(engine, traffic)
    log(f"[setup] engine and shapes warm at {time.perf_counter() - T_PROCESS:.1f}s; "
        f"pool {engine.n_pages} pages x {engine.kv_page_bytes} B")

    seconds = min(args.seconds, TRACE_S) if args.trace else args.seconds
    for p, b in traffic.first_wave():
        drv.send(p, b)
    for _ in range(int(mix["warm_decode_steps"])):
        drv.step()
    setup_s = time.perf_counter() - T_PROCESS
    log(f"[setup] {mix['warm_decode_steps']} warm decode steps done at {setup_s:.1f}s; "
        f"{events[COMPILE_EVENT]} programs compiled or loaded in set-up, "
        f"{events[CACHE_HIT_EVENT]} of them from the persistent cache")

    trace_dir = ROOT / "bench_out" / f"trace-{cell['name']}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python function events: they would be most of the file
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    lowered_before = events[LOWERING_EVENT]
    n_before = len(drv.steps)
    t0 = time.perf_counter()
    with (jax.profiler.TraceAnnotation("bench.window") if args.trace
          else contextlib.nullcontext()):
        while time.perf_counter() < t0 + seconds:
            drv.step()
    steps = drv.steps[n_before:]
    t1 = steps[-1].t
    window_compiles = events[LOWERING_EVENT] - lowered_before
    if args.trace:
        jax.profiler.stop_trace()

    late = [c.submitted - c.due for c in drv.clients
            if c.submitted is not None and t0 <= c.due <= t1]
    if late:
        log(f"[generator] {len(late)} requests sent in the window; submitted late by "
            f"median {1e3 * sorted(late)[len(late) // 2]:.3f} ms, "
            f"max {1e3 * max(late):.3f} ms")
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"[window] {t1 - t0:.3f}s, {len(steps)} steps, {sum(s.tokens for s in steps)} tokens; "
        f"{window_compiles} programs lowered in the window")
    finished = [c.req for c in drv.clients if c.req.done]
    attempted = sum(1 for c in drv.clients if c.submitted is not None)
    failed = sum(1 for c in drv.clients if c.req.finished and not c.req.done)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak_bytes}

    trace = tr.load(tr.find_trace_file(trace_dir)) if args.trace else None
    peaks = pk.peaks_for(dev.device_kind) if args.trace else None
    run = RunData(conf, peaks, trace, drv.clients, steps, t0, t1)
    if args.trace:
        metrics = read_metrics(cell_metrics(bench, cell, "per_layer"), run)
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": tr.top_ops(trace), "idle_gaps": tr.idle_gaps(trace)}
    else:
        metrics = read_metrics(cell_metrics(bench, cell, "end_to_end"), run)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        breakdown = None

    # free the engine's state before the reference runs
    del drv.engine, engine
    gc.collect()
    picked = check.sample(finished, args.seed, min_tokens=limits["min_tokens"],
                          min_requests=limits["min_requests"])
    t_ref = time.perf_counter()
    read = check.served_gaps(ref, conf, weights, picked,
                             t_pad=conf["engine"]["max_seq"], control=control)
    gap = read.get("control_mean_gap" if control else "mean_gap", float("inf"))
    n_cmp = read["n"]
    log(f"[check] reference over {len(picked)} requests, {n_cmp} served tokens, "
        f"{time.perf_counter() - t_ref:.1f}s; readings: "
        + ", ".join(f"{k} {v}" for k, v in read.items() if k != "n"))
    checks = {
        "mean_gap": {"value": gap, "limit": float(limits["mean_gap_limit"])},
        "served_tokens": {"value": n_cmp, "limit": limits["min_tokens"]},
        "failed": {"value": failed, "limit": 0},
    }
    correct = (gap <= checks["mean_gap"]["limit"] and n_cmp >= limits["min_tokens"]
               and failed == 0)
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device, "window_compiles": window_compiles,
           "readings": read}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
