"""``BENCHMARK.json`` read against the harness's files: every name resolves,
names and units keep to their alphabet, every per-layer metric's cells
report the end-to-end metric it moves, a new configuration, mix or metric is
found from files alone, and the command refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT
BENCH = harness.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
#: with the cells measured and not admitted (``pending.json``)
ALL = harness.load_bench(pending=True)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


@pytest.mark.parametrize("cell", [w["name"] for w in ALL["workloads"]])
def test_cell_resolves_by_name(cell):
    c = harness.resolve_cell(ALL, cell)
    assert os.path.exists(os.path.join(harness.HERE, "refs",
                                       c.config["arch"] + ".py"))
    assert os.path.exists(os.path.join(harness.HERE, "kinds",
                                       c.traffic["kind"] + ".py"))
    for m in c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer


@pytest.mark.parametrize("bench", [BENCH, ALL], ids=["admitted", "pending"])
def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/")
        assert harness.load_json(os.path.join(ROOT, c["file"]))["name"] \
            == c["name"]
    metrics = [n for g, n in names if g in ("end_to_end", "per_layer")]
    assert len(metrics) == len(set(metrics))


def test_configuration_files_state_their_cuts():
    """``reduced`` holds cuts of scale alone; where the program computes
    something else than the published model, the file names the key under
    ``departures`` with both values, and holds the value it runs."""
    for c in ALL["configs"]:
        f = harness.load_json(os.path.join(ROOT, c["file"]))
        assert f["reduced"] == c["reduced"]
        assert f["source"] == c["source"]
        for k in f["reduced"]:
            assert k in f and k in f["published"]
        departures = dict(f.get("departures", {}))
        assert departures.pop("why", "x")
        for k, d in departures.items():
            assert k not in f["reduced"]
            assert f[k] == d["run"] != d["published"], k


def test_limits_are_set():
    for c in BENCH["configs"]:
        f = harness.load_json(os.path.join(ROOT, c["file"]))
        assert f["limits"] and all(isinstance(v, float) and v > 0
                                   for v in f["limits"].values())


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS
            reported = {x["name"] for x in
                        harness.metrics_of_cell(BENCH, "end_to_end", cell)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert all(1 <= len(k) <= 200 for k in layers)


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


NEW = """
import json, os, sys
sys.path.insert(0, os.getcwd())
from chipbench import harness
bench = harness.load_bench(os.getcwd())
cell = harness.resolve_cell(bench, "extra-cell", os.getcwd())
reader = harness.load_module("metrics", "extra_metric.decode")
print(json.dumps({"config": cell.config["name"],
                  "kind": cell.traffic["kind"],
                  "per_layer": [m["name"] for m in cell.per_layer],
                  "read": reader.read({"kind": "decode"}, None)}))
"""


def test_new_files_are_found_without_editing_any(tmp_path):
    """A later change adds a configuration, a mix and a metric as files and
    entries; the harness finds them by name and no existing file changes."""
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: (tmp_path / "chipbench" / p).read_bytes()
              for p in _files(tmp_path / "chipbench")}
    bench = json.loads(json.dumps(BENCH))
    cfg = dict(harness.load_json(os.path.join(ROOT, bench["configs"][0]
                                              ["file"])), name="extra-config")
    (tmp_path / "chipbench/configs/extra-config.json").write_text(
        json.dumps(cfg))
    traffic = dict(harness.load_json(harness.traffic_path(
        bench["workloads"][0]["traffic"])), batch=8)
    (tmp_path / "chipbench/traffic/extra-mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "chipbench/metrics/extra_metric.decode.py").write_text(
        "def read(record, trace):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="extra-config",
                                 file="chipbench/configs/extra-config.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="extra-cell",
                                   config="extra-config",
                                   traffic="extra-mix"))
    first = bench["workloads"][0]["name"]
    moves = next(m["name"] for m in bench["end_to_end"]
                 if first in m.get("workloads", []))
    for m in bench["end_to_end"]:
        if first in m.get("workloads", []):
            m["workloads"].append("extra-cell")
    bench["per_layer"].append({
        "name": "extra_metric.decode", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "replica loop", "moves": moves,
        "workloads": ["extra-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, "-c", NEW], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["config"] == "extra-config"
    assert got["kind"] == traffic["kind"]
    assert "extra_metric.decode" in got["per_layer"]
    assert got["read"] == 42.0
    for p, data in before.items():
        assert (tmp_path / "chipbench" / p).read_bytes() == data


def _files(base):
    return [os.path.relpath(os.path.join(d, f), base)
            for d, _, fs in os.walk(base) for f in fs]


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout
