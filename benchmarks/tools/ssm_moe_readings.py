"""Read the ends that the limits of the Mamba-2 + many-expert cell's outputs
check are set from, on the chip, at the cell's own size and load. A tool; the
benchmark's own runs never run it.

    python3 benchmarks/tools/ssm_moe_readings.py --workload <cell> \
        --seeds 101,102,... --control-seeds 3 --seconds 12 \
        --controls int8,state_bf16,top9,softmax_all,no_shared,res_1,scale_sqrt,rope,norm_then_gate

One process: for every seed, the model with that seed's weights, a fresh
server, a short window at the cell's own load, and the PROGRAM's readings —
the gap of the served tokens below the float32 reference's best (its widest
and its percentiles, ``drivers/serve_latent_moe.py::served_gap_stats``) and
the drift of the probed slots' SSM state
(``drivers/serve_ssm_moe.py::state_drifts``). For the first
``--control-seeds`` seeds also each CONTROL's readings over the same prompts,
served tokens and consumed tokens: the reference in a lower precision, or
with a planted fault, put in the program's place (the float32 reference's
pass is made once and shared by all controls; the state is read again only
for the controls that act on it, ``--state-controls``); and three faults planted
in what the probe read — the state restored as zeros, the state of another
request, and a state that missed its last 128 tokens (not carried over a
chunk's edge). One JSON line per seed on standard output.
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


def planted(run: dict, pad_to: int, stale: int = 128) -> dict:
    """The first probed slot with a fault planted in what the probe read,
    by the fault's name."""
    import numpy as np

    from benchmarks.reference import ssm_moe_lm as ref

    a = run["state_probe"][0]
    old = ref.states_at(run["weights"], run["config"], a["tokens"][:-stale],
                        pad_to)
    return {
        "zeroed": dict(a, h={i: np.zeros_like(h) for i, h in a["h"].items()}),
        "other_request": dict(a, tokens=a["tokens"][::-1]),
        f"stale_{stale}": dict(a, h=old)}


def control_gaps(run: dict, limits: dict, seed: int, modes) -> dict:
    """``served_gap_stats`` of every control in ``modes`` — the gap, in the
    float32 reference, of the token each control puts first — with ONE
    float32 pass a sampled request shared by all of them."""
    import importlib

    import numpy as np

    from benchmarks import stats
    from benchmarks.check_served import sample_finished

    ref = importlib.import_module(
        f"benchmarks.reference.{run['config']['reference']}")
    cfg, weights, pad_to = run["config"], run["weights"], int(limits["pad_to"])
    gaps = {m: [] for m in modes}
    for idx in sample_finished(run, seed, int(limits["sample_requests"])):
        seq, p = run["results"][idx], run["prompts"][idx]
        served = seq[len(p):]
        _, lg = ref.served_gaps(weights, cfg, p, served, pad_to=pad_to)
        for m in modes:
            _, low = ref.served_gaps(weights, cfg, p, served, pad_to=pad_to,
                                     mode=m)
            g = lg.max(-1) - lg[np.arange(len(served)), low.argmax(-1)]
            gaps[m].append(np.where(np.isfinite(g), g, np.inf))
    out = {}
    for m, parts in gaps.items():
        g = np.concatenate(parts) if parts else np.asarray([np.inf])
        out[m] = {"max": float(g.max()), "tokens": int(len(g)),
                  "argmax_share": float((g == 0).mean()),
                  **{f"p{q:g}": float(stats.percentile(g.tolist(), q))
                     for q in (50, 90, 95, 99)}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--controls", default="int8,state_bf16")
    ap.add_argument("--state-controls", default="int8,state_bf16",
                    help="the controls whose state is read too")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    import jax

    from benchmarks import run as R
    from benchmarks.drivers import serve_ssm_moe as driver

    _, _, config, traffic, limits, chips = R.load_cell(ROOT, args.workload)
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = R.Context(workload=args.workload, seed=seed,
                        seconds=args.seconds, trace=False, config=config,
                        traffic=traffic, chips=chips,
                        t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"))
        run = driver.run(ctx)
        line = {"seed": seed, "finished": len(run["results"]),
                "probed": [len(p["tokens"]) for p in run["state_probe"]],
                "program": dict(
                    driver.served_gap_stats(run, limits, seed, log=R.log),
                    **driver.state_drifts(run, limits, log=R.log))}
        if i < args.control_seeds:
            modes = args.controls.split(",")
            line.update(control_gaps(run, limits, seed, modes))
            for mode in modes:
                if mode in args.state_controls.split(","):
                    line[mode].update(driver.state_drifts(run, limits,
                                                          mode=mode))
                R.log(f"control {mode}: {json.dumps(line[mode])}")
            if run["state_probe"]:
                for name, probe in planted(run, int(limits["pad_to"])
                                           ).items():
                    line[name] = driver.state_drifts(
                        dict(run, state_probe=[probe]), limits)
                    R.log(f"planted {name}: {json.dumps(line[name])}")
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
