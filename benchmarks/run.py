"""Run ONE cell of the benchmark once, in this process, on this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, measure for ``--seconds``, check the outputs against the plain
reference, print one JSON object as the last line of standard output, exit.
No TPU, fewer chips than the cell asks for, or a ``device_kind`` that
``benchmarks/peaks.json`` does not list is an error and a non-zero exit with
no result line — never a fallback.

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic; ``configs/<config>.json`` names its driver and its
reference; ``traffic/<traffic>.json`` names its generator; ``limits/<cell>.json``
holds the limits of the outputs check; each per-layer metric has a reader
``layer_metrics/<metric>.py``. This file hard-codes no cell and no metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str = "") -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    chips: int
    t_process_start: float
    scratch_dir: str
    rate_rps: Optional[float] = None      # the knee sweep only
    log: Callable[[str], None] = field(default=log)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def cell_of(manifest: dict, workload: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in manifest['workloads']]}")


def load_cell(root: str, workload: str):
    """(manifest, base directory, configuration, traffic, limits, chips) of
    one cell: every file found by the names in ``BENCHMARK.json``."""
    manifest = load_manifest(root)
    cell = cell_of(manifest, workload)
    base = os.path.join(root, manifest["paths"][0])
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    return (manifest, base, load_json(root, cfg_entry["file"]),
            load_json(base, "traffic", cell["traffic"] + ".json"),
            load_json(base, "limits", workload + ".json"), int(cell["chips"]))


def metrics_of(manifest: dict, group: str, workload: str):
    """The metrics of ``group`` that this cell reports: those that list it
    under ``workloads``, and those with no such key."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def data_file(base: str, *parts) -> str:
    """A data file of the benchmark: under ``base`` (the manifest's first
    path), else beside this file (fixture manifests add to, and do not copy,
    what is here)."""
    path = os.path.join(base, *parts)
    return path if os.path.exists(path) else os.path.join(HERE, *parts)


def load_reader(name: str, base: str = HERE):
    path = data_file(base, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache(base: str = HERE) -> str:
    """JAX's persistent compilation cache at a FIXED path inside the checkout
    (the path is part of the cache key), or where JAX_COMPILATION_CACHE_DIR
    says. Every program is cached, however fast it compiled, so that a second
    run finds them all."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(base, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_stamp(chips: int, peaks_table: dict, require_chip: bool):
    import jax

    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": chips}
    if not require_chip:
        return stamp, next(v for k, v in peaks_table.items()
                           if not k.startswith("_"))
    if stamp["platform"] != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{stamp['platform']!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); JAX sees "
                         f"{len(devs)}")
    if stamp["kind"] not in peaks_table:
        raise SystemExit(f"device_kind {stamp['kind']!r} is not in "
                         f"benchmarks/peaks.json: add its published peaks "
                         f"with their source")
    return stamp, peaks_table[stamp["kind"]]


def reduce_trace(run: dict, chips: int, require_chip: bool = True
                 ) -> Optional[dict]:
    """Device busy time, top ops and labelled idle gaps of the traced
    window; fills ``run['device_ops']`` for the roofline readers."""
    from benchmarks import trace_reduce as tr

    if not run.get("trace_dir"):
        return None
    planes = tr.load(tr.find_xplane(run["trace_dir"]))
    shutil.rmtree(run["trace_dir"], ignore_errors=True)
    names = tr.device_planes(planes)[:chips]
    if not names and not require_chip:
        return None                      # a CPU rehearsal has no device plane
    if not names:
        raise RuntimeError(f"no device plane in the trace; planes: "
                           f"{sorted(planes)}")
    busy = [tr.busy_seconds(tr.ops(planes, n)) for n in names]
    fullest = names[busy.index(max(busy))]
    run["device_ops"] = tr.ops(planes, fullest)
    a, b = run["traced_window"]
    return {"busy_s": sum(busy) / len(busy), "window_s": b - a,
            "device_ops": tr.top_ops(run["device_ops"]),
            "idle_gaps": tr.label_gaps(run["device_ops"],
                                       tr.modules(planes, fullest))}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_chip: bool = True,
             rate_rps: Optional[float] = None) -> dict:
    """Everything but the command line: returns the result object. Tests
    call it with ``require_chip=False`` on fixture cells."""
    manifest, base, config, traffic, limits, chips = load_cell(root, workload)

    # (no cache in a CPU rehearsal: XLA:CPU programs are cheap to rebuild
    # and their reload warns about host features)
    cache = enable_compile_cache(base) if require_chip else None
    stamp, peaks = device_stamp(chips, load_json(data_file(base, "peaks.json")),
                                require_chip)
    log(f"platform={stamp['platform']} device_kind={stamp['kind']} "
        f"chips={chips} compile cache: {cache}")
    ctx = Context(workload=workload, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), config=config, traffic=traffic,
                  chips=chips, t_process_start=T_PROCESS_START,
                  scratch_dir=os.path.join(base, ".scratch"),
                  rate_rps=rate_rps)
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    run = driver.run(ctx)
    run["chips"], run["peaks"] = chips, peaks
    log(f"window closed; set-up {run['setup_s']:.2f}s; implementations "
        f"selected: {json.dumps(run.get('selected', {}), sort_keys=True)}")
    log(driver.describe(run))

    device = dict(stamp, memory_peak_bytes=run["memory_peak_bytes"])
    breakdown = None
    if trace:
        red = reduce_trace(run, chips, require_chip)
        if require_chip and (red is None or red["busy_s"] <= 0):
            raise RuntimeError("the traced run saw no operation on the device")
        breakdown = {"selected": run.get("selected", {})}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown.update(device_ops=red["device_ops"],
                             idle_gaps=red["idle_gaps"])
    run["device"] = device

    metrics = {}
    if trace:
        for m in metrics_of(manifest, "per_layer", workload):
            value = load_reader(m["name"], base).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run.get("roofline_bounds"):
            breakdown["roofline_bounds"] = run["roofline_bounds"]
    else:
        e2e = driver.end_to_end(run)
        for m in metrics_of(manifest, "end_to_end", workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    attempted, failed = driver.attempted_failed(run)
    t_ref = time.monotonic()
    correct, compared = driver.check(run, limits, int(seed), log=log)
    log(f"reference and comparison took {time.monotonic() - t_ref:.1f}s")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return _finite(result)


def _finite(o):
    """JSON has no inf or nan: a number that is one is written as 1e30."""
    if isinstance(o, dict):
        return {k: _finite(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_finite(v) for v in o]
    if isinstance(o, float) and (o != o or o in (float("inf"),
                                                 float("-inf"))):
        return 1e30
    return o


def print_compared(compared: dict) -> None:
    for name, c in compared.items():
        rel = ">=" if c.get("at_least") else "<="
        log(f"compared {name}: {c['value']!r} (limit {rel} {c['limit']!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # pin the platform BEFORE jax is imported: an unlisted platform falls
    # back to the CPU with a warning, and a CPU number must never be printed
    # under a device metric's name
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
