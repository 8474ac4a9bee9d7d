"""The control of a cell's comparison, run by hand on the chip:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one short run of the cell as ``bench/run.py`` makes it, in
one process, with the fp8 control in the served tokens' place: the
reference with fp8 operands in every matmul picks its first choice at each
position of the sampled requests, and the mean gap of those choices below
the float32 reference's best logit is compared with the cell's limit
(``bench/cells/<cell>.json``), so ``correct`` must come out false.  The
program's own mean gap is printed beside it.  The limit lies between the
largest program reading and the smallest control reading (PERF.md).
Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, load_limits, run_cell, use_compile_cache  # bench/ is on the path as the script's dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    from bench import model, traffic as tf

    conf, mix = model.load_config(cell["config"]), tf.load_mix(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        out = run_cell(bench, cell, conf, mix, load_limits(cell["name"]), run_args,
                       control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": {k: v["value"] for k, v in out["checks"].items()},
                          "readings": out["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
