"""The benchmark is driven by data: every cell resolves its configuration,
traffic and metric readers by name, new files are found without editing
old ones, and BENCHMARK.json keeps to the contract's shapes."""
import json
import os
import re
import shutil

import pytest

from chipbench import spec, traffic

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("name", [m["name"] for m in METRICS] + CELLS
                         + [c["name"] for c in BENCH["configs"]])
def test_names_obey_the_charset(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_units_and_direction(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.resolve(cell)
    traffic.check(c.traffic)
    assert c.config["num_features"] > 0
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        entry = next(e for e in BENCH["per_layer"] if e["name"] == m.name)
        assert entry["moves"] in names, (m.name, entry["moves"])
    for m in c.end_to_end + c.per_layer:
        assert callable(m.read)


def test_config_files_are_named_in_benchmark():
    for c in BENCH["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        assert "limits" in config and "assumed" in config


def test_new_files_are_found_without_edits(tmp_path):
    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(spec.HERE, sub), base / sub)
    cfg = json.loads((base / "configs" / "krr-d16k.json").read_text())
    (base / "configs" / "krr-new.json").write_text(json.dumps(
        dict(cfg, num_features=512)))
    (base / "traffic" / "fit-new.json").write_text(json.dumps(
        {"kind": "fit", "num_iters": 50}))
    (base / "metrics" / "new_metric.fit_new.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "krr-new", "source": "x",
                             "file": "chipbench/configs/krr-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.fit", "config": "krr-new",
                               "traffic": "fit-new", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric.fit_new", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "setup_s",
                               "workloads": ["new.fit"]})
    c = spec.resolve("new.fit", bench=bench, base=str(base))
    assert c.config["num_features"] == 512
    assert c.traffic["num_iters"] == 50
    assert [m.name for m in c.per_layer] == ["new_metric.fit_new"]
    assert c.per_layer[0].read(None) == 42.0


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        spec.resolve("no.such-cell")
