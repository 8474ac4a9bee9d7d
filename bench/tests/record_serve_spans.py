"""Records the trace ``bench/tests/data/serve_spans.xplane.pb`` on a TPU: a
2-layer model at Qwen2-7B widths with 8 slots, 7 requests decoding when the
window opens; inside the ``bench.window`` span one more request is sent
(``bench.submit``) and 6 engine steps run under ``bench.step`` spans, the
first of them admitting it (one prefill).  Every program and splice shape
is warmed before the window; the Python tracer is off.

    python3 bench/tests/record_serve_spans.py [out.xplane.pb]
"""
from __future__ import annotations

import copy
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PROMPT = 200     # tokens per prompt: bucket 256, one complete 128-token block
SLOTS = 8
STEPS = 6        # engine steps in the window


def main(argv=None) -> int:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import model, spans, trace as tr
    from repro.serve.engine import Request

    argv = sys.argv[1:] if argv is None else argv
    out = pathlib.Path(argv[0]) if argv else (
        ROOT / "bench_out" / "serve_spans.xplane.pb")
    conf = copy.deepcopy(model.load_config("qwen2-7b"))
    conf["num_hidden_layers"] = 2
    conf["engine"] = dict(conf["engine"], slots=SLOTS, max_seq=512)
    weights = model.reference_module(conf).init_weights(conf, model.jax_key(7))
    engine = model.build_engine(conf, model.build_program(conf), weights)
    rng = np.random.default_rng(7)
    uid = iter(range(100))

    def send(budget):
        engine.submit(Request(uid=next(uid), max_new_tokens=budget,
                              prompt=rng.integers(0, conf["vocab_size"], PROMPT)
                              .astype(np.int32)))

    # warm: a one-row admission (the window's shape) to its end, then the
    # seven requests that decode through the window
    send(3)
    while engine._has_work():
        engine.step()
    for _ in range(SLOTS - 1):
        send(100)
    for _ in range(4):
        engine.step()

    trace_dir = ROOT / "bench_out" / "trace-serve-spans"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.submit"):
            send(50)
        for _ in range(STEPS):
            with TraceAnnotation("bench.step"):
                engine.step()
    jax.profiler.stop_trace()

    path = tr.find_trace_file(trace_dir)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    trace = tr.load(out)
    sp = spans.build(trace, spans.read_host(out)[1])
    print(f"{out}: {out.stat().st_size} bytes, window {trace.window_s:.4f} s, "
          f"busy {tr.busy_s(trace):.4f} s")
    print("programs:", sorted({m.name.split("(")[0] for d in trace.devices
                               for m in d.modules}))
    print(f"{len(sp.cycles)} cycles, {len(sp.decode_cycles())} decode, prefills "
          f"{[s.stats for s in sp.named('serve.prefill')]}")
    for name, sec in sp.idle_by_span:
        print(f"  idle {name:24s} {sec * 1e3:9.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
