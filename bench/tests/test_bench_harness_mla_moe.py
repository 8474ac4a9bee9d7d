"""The harness end to end on the CPU for the ``mla_moe`` family at tiny
widths (``tiny_mla_moe.py``): a sound run is correct; the fp8 control and
each fault the timed path can have (a decode step that returns its state
unchanged, a token altered where it is produced) make it incorrect.  The
tiny limit on the mean gap sits between the tiny program's readings (at
most 0.026 over seeds 11-14) and the tiny control's (at least 0.108)."""
import json
import pathlib

import pytest

from bench import run
from bench.tests import tiny, tiny_mla_moe
from bench.tests.test_bench_harness import _state_unchanged, _token_altered

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY_LIMITS = {"mean_gap_limit": 0.06, "min_tokens": 40, "min_requests": 2}


def _run(**kw):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["deepseek-v3.reason"]
    return run.run_cell(bench, cell, tiny_mla_moe.tiny_config(), tiny.tiny_mix("reason"),
                        TINY_LIMITS, tiny.args(seed=11, seconds=1.5), **kw)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["window_compiles"] == 0
    assert out["failed"] == 0 and out["attempted"] >= 4


def test_fp8_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > TINY_LIMITS["mean_gap_limit"]
    assert out["readings"]["mean_gap"] <= TINY_LIMITS["mean_gap_limit"]


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(fault):
    out = _run(engine_hook=fault)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > TINY_LIMITS["mean_gap_limit"]
