"""The harness end to end on the CPU at tiny widths (``tiny.py``): a sound
run is correct; the fp8 control and each fault the timed path can have
(a decode step that returns its state unchanged, a token altered where it
is produced) make it incorrect; without a chip the command prints no
result.  The tiny limit on the mean gap sits between the tiny program's
readings (at most 0.0037 over seeds 11-14) and the tiny control's (at
least 0.0239)."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY_LIMITS = {"mean_gap_limit": 0.012, "min_tokens": 40, "min_requests": 2}


def _run(mix=None, **kw):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["qwen2-7b.reason"]
    return run.run_cell(bench, cell, tiny.tiny_config("qwen2-7b"),
                        mix or tiny.tiny_mix("reason"), TINY_LIMITS,
                        tiny.args(seed=11, seconds=1.5), **kw)


def test_sound_run_is_correct_and_prints_its_checks_last():
    out = _run()
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["window_compiles"] == 0
    assert out["failed"] == 0 and out["attempted"] >= 4


def test_fp8_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] == out["readings"]["control_mean_gap"]
    assert out["checks"]["mean_gap"]["value"] > TINY_LIMITS["mean_gap_limit"]
    assert out["readings"]["mean_gap"] <= TINY_LIMITS["mean_gap_limit"]


def _state_unchanged(engine):
    step = engine._step
    engine._step = lambda p, s, t: (step(p, s, t)[0], s)


def _token_altered(engine):
    step = engine._step

    def altered(p, s, t):
        logits, state = step(p, s, t)
        return logits.at[..., 7].add(1e4), state

    engine._step = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(fault):
    out = _run(engine_hook=fault)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > TINY_LIMITS["mean_gap_limit"]


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-7b.reason",
         "--seed", "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_chip_no_result():
    res = _command(ROOT)
    assert res.returncode != 0
    assert res.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _command(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
