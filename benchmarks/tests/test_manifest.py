"""BENCHMARK.json against the contract's limits, and against the files that
the harness finds by name."""
import json
import os
import re

import pytest

from conftest import FIXTURES, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok", re.I)


def manifests():
    return [os.path.join(ROOT, "BENCHMARK.json"),
            os.path.join(FIXTURES, "BENCHMARK.json")]


@pytest.fixture(params=manifests(), ids=["repo", "fixture"])
def loaded(request):
    with open(request.param) as f:
        return os.path.dirname(request.param), json.load(f)


def one_line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_keys_names_units(loaded):
    root, m = loaded
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])
    assert len(m["command"]) <= 32 and all(one_line(w) for w in m["command"])
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)), f"duplicate name in {group}"
        assert all(NAME.match(n) for n in names)
    for met in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(met["unit"]), met
        assert met["better"] in ("lower", "higher")
        assert met["source"] in SOURCES
    for met in m["end_to_end"]:
        assert set(met) <= {"name", "unit", "better", "bound", "source",
                            "workloads"}
        assert met["source"] in ("host_clock", "device_trace")
        assert 0.01 <= met["bound"] <= 0.1
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in m["end_to_end"])
    for met in m["per_layer"]:
        assert set(met) <= {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
        assert one_line(met["layer"])


def test_configs_and_cells(loaded):
    root, m = loaded
    files = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["why"]) and one_line(c["source"])
        assert any(c["file"].startswith(p.rstrip("/") + "/") for p in m["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(root, c["file"])) as f:
            json.load(f)
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)], \
            "reduced may never name a width"
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    four = 0
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert w["config"] in {c["name"] for c in m["configs"]}
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
    assert four <= max(1, len(m["workloads"]) // 4)


def test_every_cell_reports_setup_one_more_and_a_layer_metric(loaded):
    _, m = loaded

    def reported(group, cell):
        return [e["name"] for e in m[group]
                if "workloads" not in e or cell in e["workloads"]]

    cells = [w["name"] for w in m["workloads"]]
    for cell in cells:
        e2e = reported("end_to_end", cell)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reported("per_layer", cell)
    e2e_names = {e["name"] for e in m["end_to_end"]}
    for met in m["end_to_end"] + m["per_layer"]:
        for cell in met.get("workloads", []):
            assert cell in cells
    for met in m["per_layer"]:
        assert met["moves"] in e2e_names and met["moves"] != "setup_s"
        for cell in met.get("workloads", cells):
            assert met["moves"] in reported("end_to_end", cell), \
                f"{met['name']} moves {met['moves']}, which {cell} does not report"


def test_every_name_finds_its_file(loaded):
    from benchmarks import run as R

    root, m = loaded
    base = os.path.join(root, m["paths"][0])
    for w in m["workloads"]:
        traffic = R.load_json(base, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "generators", traffic["generator"] + ".py"))
        R.load_json(base, "limits", w["name"] + ".json")
    for c in m["configs"]:
        cfg = R.load_json(root, c["file"])
        for kind in ("driver", "reference"):
            folder = "drivers" if kind == "driver" else "reference"
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", folder, cfg[kind] + ".py"))
    for met in m["per_layer"]:
        assert callable(R.load_reader(met["name"], base).read)


def test_run_seconds_fits_the_full_check_with_24_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_roofline_and_mfu_naming():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    roof = [p for p in m["per_layer"] if p["name"].endswith("_roofline")]
    assert roof and all(p["unit"] == "%" for p in roof)
    for p in roof:
        # beside every roofline, the whole step's share of the peak, moving
        # the same end-to-end metric in the same cells
        assert any("mfu" in re.split(r"[._\-]", q["name"])
                   and q["moves"] == p["moves"]
                   and set(p["workloads"]) <= set(q["workloads"])
                   for q in m["per_layer"]), p["name"]
