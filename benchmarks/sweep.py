"""Find the knee of a serving mix: the highest arrival rate the system
sustains with no growing backlog. A tool, run once on the chip when a mix is
defined (or when an optimisation has moved the knee); not part of any run.

    python3 benchmarks/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 25 [--burst 0]

Reuses ``run.py``'s set-up and the driver's measuring loop: the model is built
once, and every rate gets a fresh server and the schedule of ``--seed`` at
that rate. Prints one line per rate: tokens per second completed, the slope
of the requests in the system (queued + in a slot) over the window (per second; about 0 below the
knee, rate minus capacity above it), the least and the last queue depth, and
the tails.

How a rate is derived from it (numbers: PERF.md section 4):

- The knee is the highest rate whose backlog slope is <= 0 with nothing
  queued at the window's end, bracketed to 0.25 /s. Sweep with ``--burst 0``:
  a mix whose ramp opens with a burst that fills every slot still holds that
  burst's backlog at the end of a 25 s window at any rate worth trying, so
  with the burst the criterion measures the burst, not the server.
- A latency cell (``unfinished_fails`` true) is offered about 0.8 x the knee.
- A capacity cell (``unfinished_fails`` false, judged on ``serve_tok_s``) is
  offered ``knee_factor`` = 1.5 x the knee, with its burst: the window opens
  on every slot full and a queue of about ramp x rate behind them, so the
  slots stay backed until capacity has grown to about 1.8 x today's. Above
  the knee the offered rate does not enter the result; show it once
  (``--rates`` 1.25, 1.5 and 2.0 x the knee, ``--seconds 50``, the file's
  burst: ``serve_tok_s`` within 1.5 % of each other, occupancy >= 99 %).
- When to sweep again: ``queue_depth_min.decode`` (the least queue depth over
  the window, a per-layer metric of every capacity cell) says how much of
  that room is left. Under about 10, re-sweep and reset ``rate_rps`` in a
  ``benchmark`` PR; at 0 a slot has waited for work and ``serve_tok_s`` is
  the offered load, not a capacity (a faster server then reads LOWER).
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--burst", type=int, default=None,
                    help="override the ramp's burst (0 to find a knee)")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    from benchmarks import readers, stats
    from benchmarks import run as R

    _, _, config, traffic, _, chips = R.load_cell(ROOT, args.workload)
    if args.burst is not None:
        traffic = dict(traffic, ramp=dict(traffic.get("ramp", {}),
                                          burst=args.burst))
    R.enable_compile_cache(HERE)
    R.device_stamp(chips, R.load_json(HERE, "peaks.json"), True)
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    model, _ = driver.build_model(config, args.seed)
    for rate in [float(x) for x in args.rates.split(",")]:
        ctx = R.Context(workload=args.workload, seed=args.seed,
                        seconds=args.seconds, trace=False, config=config,
                        traffic=traffic, chips=chips,
                        t_process_start=R.T_PROCESS_START,
                        scratch_dir=os.path.join(HERE, ".scratch"),
                        rate_rps=rate)
        srv = driver.build_server(model, config, telemetry=False)
        run = driver.measure(ctx, srv)
        del srv
        gc.collect()
        e2e = driver.end_to_end(run)
        st = [s for s in run["steps"] if 0 <= s["t1"] < args.seconds]
        slope = stats.backlog_slope([(s["t1"], s["queue_depth"] + s["slots_occupied"])
                                     for s in st])
        attempted, failed = driver.attempted_failed(run)
        print(json.dumps({
            "rate_rps": rate, "serve_tok_s": e2e["serve_tok_s"],
            "backlog_slope_rps": slope,
            "queue_depth_min": readers.queue_depth_min(run),
            "queue_depth_end": st[-1]["queue_depth"] if st else None,
            "ttft_p95_ms": e2e.get("ttft_p95_ms"),
            "tpot_p95_ms": e2e.get("tpot_p95_ms"),
            "occupancy": stats.mean([s["slots_occupied"] / s["slots_total"]
                                     for s in st]) if st else None,
            "tick_ms": (sum(s["t1"] - s["t0"] for s in st) / len(st) * 1e3
                        if st else None),
            "attempted": attempted, "failed": failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
