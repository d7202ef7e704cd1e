"""The harness finds every cell, configuration, traffic file and metric
of BENCHMARK.json by name, and the file keeps to its contract's shape."""
import json
import math
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import drivers  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    spec = run.resolve(BENCHMARK, cell)
    assert issubclass(drivers.load(spec["traffic"]["kind"]), drivers.Driver)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert callable(run.load_metric(m["name"]).read)


@pytest.mark.parametrize("traffic", sorted(os.listdir(os.path.join(BENCH, "traffic"))))
def test_traffic_kind_loads(traffic):
    """Every traffic file, in a cell or kept for later, names a kind module."""
    kind = run.load_json(BENCH, "traffic", traffic)["kind"]
    assert issubclass(drivers.load(kind), drivers.Driver)


@pytest.mark.parametrize("cfg", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert set(cfg["reduced"]) == set(body["reduced"])
    assert all(k in body for k in cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in BENCHMARK["workloads"])


def test_names_units_and_limits():
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [c["name"] for c in BENCHMARK["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len(set(CELLS)) == len(CELLS)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m["workloads"]) <= set(CELLS)
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, math.floor(len(CELLS) / 2))
    assert all(len(w["why"]) <= 200 for w in BENCHMARK["workloads"] + BENCHMARK["configs"])


def test_one_layer_name_per_layer():
    by_file = {}
    for m in BENCHMARK["per_layer"]:
        by_file.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for k, v in by_file.items() if k not in ("train", "serve"))


def test_no_chip_no_result(capsys):
    """On the CPU the command exits non-zero before any work."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err
