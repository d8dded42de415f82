"""One traced run of a cell by hand, with what ``run.py`` throws away kept.
A tool; the benchmark's own runs never run it.

    python3 benchmarks/tools/traced_run.py --workload <cell> --seed <n> \
        --seconds 50 --keep <dir>

The same run as ``run.py --trace 1`` (it calls ``run.run_cell``), but the
profiler's trace directory is copied to ``--keep`` before ``run.py`` deletes
it — ``python tools/profile_step.py <dir>`` reads it — and one more line goes
to standard error: the program's own ``tick`` spans beside the benchmark's
clock around ``step()`` (``span_readers.tick_summary`` beside ``tick_ms``).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", required=True,
                    help="directory the trace directory is copied to")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

    from benchmarks import readers, run as R, span_readers

    reduce_trace = R.reduce_trace

    def keeping(run, chips, require_chip=True):
        if run.get("trace_dir"):
            shutil.rmtree(args.keep, ignore_errors=True)
            shutil.copytree(run["trace_dir"], args.keep)
        summary = span_readers.tick_summary(run)
        if summary is not None:
            outside = sorted(s["t1"] - s["t0"]
                             for s in readers.window_steps(run))
            summary["outside_mean_ms"] = readers.tick_ms(run)
            summary["outside_median_ms"] = outside[len(outside) // 2] * 1e3
            R.log("spans: " + json.dumps(summary, sort_keys=True))
        return reduce_trace(run, chips, require_chip)

    R.reduce_trace = keeping
    result = R.run_cell(args.workload, args.seed, args.seconds, True)
    R.print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
