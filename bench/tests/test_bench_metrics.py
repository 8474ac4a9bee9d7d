"""Every metric of ``BENCHMARK.json`` has its reader, found by name, and a
reader with nothing to read leaves its metric out."""
import json
import pathlib

import pytest

from bench import run

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run_data(steps, t0=10.0, t1=12.0, trace=None):
    from bench import model

    return run.RunData(model.load_config("qwen2-7b"), {"flops_bf16": 197e12,
                       "hbm_bytes_per_s": 819e9}, trace, [], steps, t0, t1)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(kind):
    for m in BENCH[kind]:
        if m["name"] != "setup_s":
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_output_tokens_per_s_counts_every_token_of_the_window():
    steps = [run.Step(10.5, 3, [5, 6, 7]), run.Step(11.0, 4, [6, 7, 8]),
             run.Step(12.0, 2, [9, 10])]
    got = run.read_metrics([{"name": "output_tokens_per_s", "unit": "tokens/s"}],
                           _run_data(steps))
    assert got == {"output_tokens_per_s": {"value": 4.5, "unit": "tokens/s"}}


def test_trace_readers_without_a_trace_leave_their_metrics_out():
    steps = [run.Step(10.5, 2, [100, 200])]
    assert run.read_metrics(BENCH["per_layer"], _run_data(steps)) == {}
