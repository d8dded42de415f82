"""Read the ends that the limits of the latent-attention + held-experts
cell's outputs check are set from, on the chip, at the cell's own size. A
tool; the benchmark's own runs never run it.

    python3 benchmarks/tools/latent_moe_readings.py --workload <cell> \
        --seeds 101,102,... --control-seeds 3 --seconds 12 \
        --controls int8,bf16,drop_1.25,top3,no_shared,k_unrotated,no_qscale

One process: for every seed, the model with that seed's weights, a fresh
server, a short window at the cell's own load, and the program's reading —
the widest gap of a served token below the float32 reference's best, and the
percentiles of that gap (``drivers/serve_latent_moe.py::served_gap_stats``).
For the first ``--control-seeds`` seeds also each CONTROL's reading: the
reference in a lower precision, or with a planted fault, put in the
program's place over the same prompts and served tokens. One JSON line per
seed on standard output. ``limit_readings.py`` reads the maximum alone; this
family's limits need the percentiles too (PERF.md section 2).
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


def one_long_request(driver, config, seed: int, shape: str) -> dict:
    """A run record (what ``served_gap_stats`` reads of one) of ONE request
    of ``shape`` = "PROMPT,ANSWER" tokens through a fresh server."""
    import numpy as np

    n_prompt, n_answer = (int(x) for x in shape.split(","))
    model, weights = driver.build_model(config, seed)
    srv = driver.build_server(model, config, telemetry=False)
    prompt = np.random.default_rng(seed).integers(
        1, config["vocab_size"], size=n_prompt).tolist()
    rid = srv.submit(prompt, max_new_tokens=n_answer, temperature=0.0)
    out = srv.run()
    return {"config": config, "weights": weights, "results": {0: out[rid]},
            "prompts": {0: prompt},
            "requests": [{"idx": 0, "max_new_tokens": n_answer}]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--sample-requests", type=int, default=0,
                    help="override the limits file's sample (0: keep it)")
    ap.add_argument("--long", default="",
                    help="PROMPT,ANSWER: instead of the cell's traffic, ONE "
                         "request of that shape per seed through a fresh "
                         "server, so that positions past the original "
                         "rope length are compared (the cell's own finished "
                         "requests end under it)")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmarks import run as R
    from benchmarks.drivers import serve_latent_moe as driver

    _, _, config, traffic, limits, chips = R.load_cell(ROOT, args.workload)
    if args.sample_requests:
        limits = dict(limits, sample_requests=args.sample_requests)
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = R.Context(workload=args.workload, seed=seed,
                        seconds=args.seconds, trace=False, config=config,
                        traffic=traffic, chips=chips,
                        t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"))
        run = (one_long_request(driver, config, seed, args.long)
               if args.long else driver.run(ctx))
        if args.long:
            limits = dict(limits, sample_requests=1, pad_to=max(
                limits["pad_to"], -(-len(run["results"][0]) // 512) * 512))
        line = {"seed": seed, "finished": len(run["results"]),
                "program": driver.served_gap_stats(run, limits, seed,
                                                   log=R.log)}
        if i < args.control_seeds:
            for mode in args.controls.split(","):
                line[mode] = driver.served_gap_stats(run, limits, seed,
                                                     mode=mode)
                R.log(f"control {mode}: {json.dumps(line[mode])}")
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
