"""Read the ends that the limits of the window + full attention,
held-experts cell's outputs check are set from, on the chip, at the cell's
own size. A tool; the benchmark's own runs never run it.

    python3 benchmarks/tools/window_moe_readings.py --workload <cell> \
        --seeds 101,102 --control-seeds 1 --controls int8,bf16,top7,...

One process: for every seed, the model with that seed's weights, a fresh
server, the cell's own window at the cell's own load, and the program's
reading — the widest gap of a served token below the float32 reference's
best at the compared positions, and the percentiles of that gap — with
the verdict of the cell's own comparison (``drivers/serve_window_moe.py``:
``gap_readings``, ``gap_summary``, ``compare``, under the limits file as it
stands). For the first ``--control-seeds`` seeds also each CONTROL's
reading and verdict: the reference in a lower precision, or with a planted
fault, put in the program's place over the same prompts and served tokens
(the float32 logits computed once a request, not once a control). One JSON
line per seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(driver, run, limits, seed, modes, log):
    """{mode: the gap's percentiles, widest and argmax share, with
    ``correct`` and the readings past their limits under ``compare``};
    mode None is the program's served tokens."""
    out = {}
    for m, g in driver.gap_readings(run, limits, seed, modes).items():
        got = driver.gap_summary(g)
        ok, compared = driver.compare(got, run, limits)
        got["correct"] = ok
        got["past_limit"] = sorted(
            k for k, c in compared.items()
            if not ((c["value"] >= c["limit"]) if c.get("at_least")
                    else (c["value"] <= c["limit"])))
        out["program" if m is None else m] = got
        log(f"{'program' if m is None else 'control ' + m}: "
            f"{json.dumps(got)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--controls", default="int8,bf16")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="the window (0: the manifest's run_seconds)")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmarks import run as R
    from benchmarks.drivers import serve_window_moe as driver

    manifest, _, config, traffic, limits, chips = R.load_cell(ROOT,
                                                              args.workload)
    seconds = args.seconds or float(manifest["run_seconds"])
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = R.Context(workload=args.workload, seed=seed, seconds=seconds,
                        trace=False, config=config, traffic=traffic,
                        chips=chips, t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"))
        run = driver.run(ctx)
        R.log(driver.describe(run))
        modes = [None] + (args.controls.split(",")
                          if i < args.control_seeds else [])
        line = {"seed": seed, "finished": len(run["results"]),
                "serve_tok_s": driver.end_to_end(run)["serve_tok_s"],
                "memory_peak_bytes": run["memory_peak_bytes"],
                **readings(driver, run, limits, seed, modes, R.log)}
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
