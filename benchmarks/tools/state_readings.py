"""Read the two ends that the limits on ``state_drift_first`` and
``state_drift_max`` are set from, on the chip, at the cell's own size and
load — ``limit_readings.py``'s twin for the comparison a hybrid cell makes
of the recurrent state itself. A tool; the benchmark's own runs never run
it.

    python3 benchmarks/tools/state_readings.py --workload <cell> \
        --seeds 101,102,... --seconds 5 --control state_bf16

One process: for every seed, the model with that seed's weights, a fresh
server, a short window at the cell's own load, then the PROGRAM's reading
(the state the probed slots hold against the float32 reference over the
tokens they consumed) and the CONTROLS' readings over the same tokens: the
reference in each ``--control`` arithmetic put in the program's place (and,
where requests finished in the window, what ``logit_gap_max`` reads of that
control), and three planted faults — the state restored as zeros, the state of another
request, and a state that missed the last 128 tokens (not carried over a
chunk's edge). One JSON line per seed on standard output.
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


def planted(run: dict, pad_to: int, stale: int = 128) -> dict:
    """The first probed slot with a fault planted in what the probe read,
    by the fault's name: the state restored as zeros, the state of another
    request (the same arrays against other tokens), and a state that
    missed its last ``stale`` tokens."""
    import numpy as np

    from benchmarks.reference import sambay_lm as ref

    a = run["state_probe"][0]
    old = ref.states_at(run["weights"], run["config"], a["tokens"][:-stale],
                        pad_to)
    return {
        "zeroed": dict(a, h={i: np.zeros_like(h) for i, h in a["h"].items()}),
        "other_request": dict(a, tokens=a["tokens"][::-1]),
        f"stale_{stale}": dict(a, h=old)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="state_bf16",
                    help="comma-separated arithmetics of the reference")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmarks import check_served
    from benchmarks import run as R

    _, _, config, traffic, limits, chips = R.load_cell(ROOT, args.workload)
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = R.Context(workload=args.workload, seed=seed,
                        seconds=args.seconds, trace=False, config=config,
                        traffic=traffic, chips=chips,
                        t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"))
        run = driver.run(ctx)
        line = {"seed": seed,
                "slots": [[p["slot"], len(p["tokens"])]
                          for p in run["state_probe"]],
                "limits": [limits["state_drift_first"],
                           limits["state_drift_max"]],
                "program": driver.state_drifts(run, limits, log=R.log)}
        for mode in args.control.split(","):
            line[f"control_{mode}"] = driver.state_drifts(run, limits,
                                                          mode=mode)
            # what the served tokens show of the same control (0.0: it
            # put the reference's own token first at every position)
            line[f"control_{mode}_logit_gap"] = (
                check_served.control_gap(run, limits, seed, mode)
                if run["results"] else None)
        for name, probe in planted(run, int(limits["pad_to"])).items():
            line[f"fault_{name}"] = driver.state_drifts(
                dict(run, state_probe=[probe]), limits)
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
