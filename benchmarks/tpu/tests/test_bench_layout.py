"""The benchmark is data found by name: adding a configuration, a traffic
mix, a cell or a metric adds files and entries and edits none.

Every name in BENCHMARK.json resolves to its files, and a copy of the
benchmark with a new configuration (``configs/gin_wide.json``,
``refs/gin_wide.py``, ``counts/gin_wide.py``), a new mix
(``mixes/trigger_burst.json``), a new cell's data
(``cells/gin_wide.trigger_burst.json``) and a new metric
(``metrics/lateness_ms.trigger.py``) loads and reports them with every
existing file unchanged.
"""

import importlib.util
import json
import re
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_its_files():
    b = bench()
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (BENCH / "refs" / f"{c['name']}.py").is_file()
        assert (BENCH / "counts" / f"{c['name']}.py").is_file()
    for w in b["workloads"]:
        assert (BENCH / "mixes" / f"{w['traffic']}.json").is_file()
        assert "mean_rel_gap" in harness.load_cell(w["name"], ROOT).limits
    for m in b["end_to_end"] + b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_names_and_units_use_the_allowed_characters():
    b = bench()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in bench()["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs


def test_every_per_layer_metric_lists_cells_that_report_what_it_moves():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["workloads"], m["name"]
        for name in m["workloads"]:
            assert name in cells, (m["name"], name)
            e2e = harness.load_cell(name, ROOT).reported(trace=False)
            assert m["moves"] in {x["name"] for x in e2e}, (m["name"], name)


def test_every_cell_reports_an_end_to_end_and_a_per_layer_metric():
    for w in bench()["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        e2e = {m["name"] for m in cell.reported(trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.reported(trace=True)


def test_additions_need_no_edit(tmp_path):
    copy = tmp_path / "benchmarks" / "tpu"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "fixtures"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    cfg = json.loads((copy / "configs" / "gin.json").read_text())
    cfg["num_layers"] = 3
    (copy / "configs" / "gin_wide.json").write_text(json.dumps(cfg))
    shutil.copy(copy / "refs" / "gin.py", copy / "refs" / "gin_wide.py")
    shutil.copy(copy / "counts" / "gin.py", copy / "counts" / "gin_wide.py")
    mix = json.loads((copy / "mixes" / "trigger.json").read_text())
    mix["warm_s"] = 0.5
    (copy / "mixes" / "trigger_burst.json").write_text(json.dumps(mix))
    (copy / "cells" / "gin_wide.trigger_burst.json").write_text(json.dumps(
        {"rate_hz": 123.0, "limits": {"mean_rel_gap": 1e-4}}))
    (copy / "metrics" / "lateness_ms.trigger.py").write_text(
        "def read(run):\n    return 1000 * float(run.lateness_s.max())\n")
    b = bench()
    b["configs"].append({"name": "gin_wide", "source": "x",
                         "file": "benchmarks/tpu/configs/gin_wide.json",
                         "reduced": ["num_layers"], "why": "x"})
    b["workloads"].append({"name": "gin_wide.trigger_burst",
                           "config": "gin_wide", "traffic": "trigger_burst",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "lateness_ms.trigger", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "client", "moves": "latency_p50_ms",
                           "workloads": ["gin_wide.trigger_burst"]})
    b["end_to_end"][1]["workloads"].append("gin_wide.trigger_burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    spec = importlib.util.spec_from_file_location("copied_harness",
                                                  copy / "harness.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["copied_harness"] = mod
    saved = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        del sys.modules["copied_harness"]
        sys.path[:] = saved
    cell = mod.load_cell("gin_wide.trigger_burst", tmp_path)
    assert cell.config["num_layers"] == 3
    assert cell.mix["rate_hz"] == 123.0 and cell.mix["warm_s"] == 0.5
    assert cell.limits == {"mean_rel_gap": 1e-4}
    assert cell.ref.__file__.endswith("gin_wide.py")
    assert [m["name"] for m in cell.reported(trace=True)] == \
        ["lateness_ms.trigger"]
    assert {m["name"] for m in cell.reported(trace=False)} == \
        {"latency_p50_ms", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before
