"""Cells, mixes and metrics are found by name; BENCHMARK.json keeps to the
shape its readers expect."""
import json
import os
import re

import pytest

from harness import registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_config_mix_and_metric_are_found_without_an_edit(tmp_path):
    """A throwaway configuration, mix and metric, written as new files, load
    by name through the unchanged harness."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "toy.json").write_text('{"n_clients": 3}')
    (bench_dir / "traffic" / "burst.json").write_text(
        '{"kind": "serve", "batch": 7}')
    (bench_dir / "limits" / "toy.burst.json").write_text('{"gap": 0.5}')
    (bench_dir / "metrics" / "toy_share.burst.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "qps", "unit": "queries/s",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "toy_share.burst", "unit": "%",
                       "moves": "setup_s"},
                      {"name": "unrelated", "unit": "%", "moves": "qps"}]}))
    cell = registry.load_cell(str(tmp_path), "toy.burst")
    assert cell.config == {"n_clients": 3}
    assert cell.traffic == {"kind": "serve", "batch": 7}
    assert cell.limits == {"gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy_share.burst"]
    read = registry.metric_reader("toy_share.burst", str(bench_dir))
    assert read({"x": 1.5}) == 3.0
    with pytest.raises(KeyError):
        registry.load_cell(str(tmp_path), "toy.missing")


def test_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()[
    "workloads"]])
def test_every_cell_loads_with_its_metrics(workload):
    b = _bench()
    cell = registry.load_cell(ROOT, workload)
    assert os.path.isfile(os.path.join(ROOT, "bench", "harness",
                                       f"{cell.traffic['kind']}.py"))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        registry.metric_reader(m["name"], os.path.join(ROOT, "bench"))
    assert set(cell.limits) and all(v >= 0 for v in cell.limits.values())
    for path in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
