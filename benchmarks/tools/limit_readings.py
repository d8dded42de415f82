"""Read the two ends that a limit of the outputs check is set from, on the
chip, at the cell's own size. A tool; the benchmark's own runs never run it.

    python3 benchmarks/tools/limit_readings.py --workload <cell> \
        --seeds 101,102,... --control-seeds 3 --seconds 12 --control int8

One process: for every seed, the model with that seed's weights, a fresh
server, a short window at the cell's own load, and the program's reading
(``logit_gap_max``). For the first ``--control-seeds`` seeds also the
CONTROL's reading: the reference in the next lower precision, put in the
program's place, over the same prompts and served tokens. One JSON line per
seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control", default="int8",
                    help="comma-separated lower precisions: int8,fp8,bf16")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmarks import check_served
    from benchmarks import run as R

    _, _, config, traffic, limits, chips = R.load_cell(ROOT, args.workload)
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = R.Context(workload=args.workload, seed=seed,
                        seconds=args.seconds, trace=False, config=config,
                        traffic=traffic, chips=chips,
                        t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"))
        run = driver.run(ctx)
        ok, compared = check_served.check(run, limits, seed, log=R.log)
        line = {"seed": seed, "correct": ok,
                "logit_gap_max": compared["logit_gap_max"]["value"],
                "tokens": compared["served_tokens_checked"]["value"],
                "wrong_shape": compared["wrong_shape"]["value"],
                "finished": len(run["results"])}
        if i < args.control_seeds:
            for mode in args.control.split(","):
                line[f"control_{mode}"] = check_served.control_gap(
                    run, limits, seed, mode)
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
