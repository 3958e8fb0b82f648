"""The harness finds every file by name, ``BENCHMARK.json`` keeps to the
naming rules, and ``bench/run.py`` refuses to run without a TPU or without
the system under test."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench_cases import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench_json():
    return harness.load_benchmark(ROOT)


def test_names_and_units_use_allowed_characters(bench_json):
    _check_names(bench_json)


def test_every_cell_resolves_and_reports_what_it_must(bench_json):
    _check_cells(bench_json)


def test_staged_cells_keep_the_same_rules():
    """The cells waiting in ``bench/staged.json`` resolve and keep the
    naming rules once merged, so that proving one only moves entries."""
    doc = harness.load_benchmark(ROOT, staged=True)
    _check_names(doc)
    _check_cells(doc)
    assert len(doc["workloads"]) > len(
        harness.load_benchmark(ROOT)["workloads"])


def _check_names(bench_json):
    metrics = bench_json["end_to_end"] + bench_json["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in bench_json["configs"]]
             + [w["name"] for w in bench_json["workloads"]]
             + [w["config"] for w in bench_json["workloads"]]
             + [w["traffic"] for w in bench_json["workloads"]]
             + [k for c in bench_json["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and len(m["unit"]) <= 16, m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)


def _check_cells(bench_json):
    e2e = {m["name"] for m in bench_json["end_to_end"]}
    for w in bench_json["workloads"]:
        cell = harness.resolve(bench_json, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names and m["moves"] in e2e
            assert callable(harness.load_reader(m["name"]))
        assert (cell.driver is not None
                or cell.traffic["load"] in harness.loads.LOADS)
        assert cell.limits


def test_new_files_are_found_by_name_alone(tmp_path, bench_json):
    """A configuration, traffic, limits and metric file dropped into their
    directories make a new cell with no edit to any file that exists."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "chicago-r32.json").read_text())
    cfg.update(name="tiny-r8", shape=[9, 8, 7], nnz=100, rank=8)
    (bench / "configs" / "tiny-r8.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (bench / "traffic" / "multistart-segment.json").read_text())
    (bench / "traffic" / "restarts-coo.json").write_text(
        json.dumps(dict(traffic, backend="coo")))
    (bench / "limits" / "tiny.coo.json").write_text(
        json.dumps({"limits": {"fit_gap": 1.0, "model_gap": 1.0}}))
    (bench / "metrics" / "fits_run.tiny.py").write_text(
        "def read(r):\n    return float(r.counters['fits'])\n")
    doc = json.loads(json.dumps(bench_json))
    doc["configs"].append({"name": "tiny-r8", "source": "test",
                           "file": "bench/configs/tiny-r8.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny.coo", "config": "tiny-r8",
                             "traffic": "restarts-coo", "chips": 1,
                             "why": "test"})
    doc["per_layer"].append({"name": "fits_run.tiny", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "fused ALS", "moves": "als_iter_s",
                             "workloads": ["tiny.coo"]})
    doc["end_to_end"][0]["workloads"].append("tiny.coo")
    cell = harness.resolve(doc, "tiny.coo", bench=bench)
    assert cell.config["shape"] == [9, 8, 7]
    assert cell.traffic["backend"] == "coo"
    assert [m["name"] for m in cell.per_layer] == ["fits_run.tiny"]
    read = harness.load_reader("fits_run.tiny", bench=bench)
    assert read(harness.Reading({"fits": 3}, None, None, None)) == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_traffic_driver_is_found_by_name(tmp_path, bench_json):
    """A mix that needs a driver of its own brings ``<mix>.py`` with a class
    ``Load``, and the harness uses it with no edit to any file."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    (bench / "traffic" / "one-fit.py").write_text(
        "class Load:\n"
        "    def __init__(self, config, traffic, seed, limits):\n"
        "        self.args = (config, traffic, seed, limits)\n")
    (bench / "limits" / "chicago-one.json").write_text(
        json.dumps({"limits": {"fit_gap": 1.0}}))
    doc = json.loads(json.dumps(bench_json))
    doc["workloads"].append({"name": "chicago-one", "config": "chicago-r32",
                             "traffic": "one-fit", "chips": 1,
                             "why": "test"})
    cell = harness.resolve(doc, "chicago-one", bench=bench)
    assert cell.traffic == {} and cell.driver.name == "one-fit.py"
    drv = harness.make_load(cell, 5)
    assert type(drv).__name__ == "Load"
    assert drv.args[0]["name"] == "chicago-r32" and drv.args[2] == 5


@pytest.mark.parametrize("law", [{"kind": "poisson"},
                                 {"kind": "onoff", "on_s": 2.0,
                                  "off_s": 3.0}])
def test_arrival_law_is_data_and_keeps_its_mean_rate(law):
    import numpy as np

    rate, n = 8.0, 4000
    a = harness.loads.arrival_offsets(law, rate, n,
                                      np.random.default_rng(1))
    b = harness.loads.arrival_offsets(law, rate, n,
                                      np.random.default_rng(2))
    assert len(a) == n and np.all(np.diff(a) >= 0) and a[0] == 0.0
    # The same law gives every seed the same number of arrivals over
    # about the same span, at the mean rate.
    assert abs(a[-1] - b[-1]) / a[-1] < 0.1
    assert abs(n / a[-1] - rate) / rate < 0.1
    if law["kind"] == "onoff":
        phase = np.mod(a, law["on_s"] + law["off_s"])
        assert np.all(phase < law["on_s"])


def test_nnz_law_is_data():
    loads = harness.loads
    uni = loads.pool_nnz(1000, {"kind": "uniform", "range": [0.9, 1.0]}, 16)
    assert uni == [int(round(1000 * (0.9 + 0.1 * (j + 0.5) / 16)))
                   for j in range(16)]
    tail = loads.pool_nnz(1000, {"kind": "lognormal", "sigma": 1.0,
                                 "range": [0.25, 4.0]}, 16)
    assert tail == sorted(tail) and tail[0] == 250 and tail[-1] == 4000
    assert tail[7] < 1000 < tail[8]
    with pytest.raises(ValueError):
        loads.pool_nnz(1000, {"kind": "zipf", "range": [1, 2]}, 4)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chicago-als.segment",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_a_tree_without_the_system(tmp_path):
    (tmp_path / "BENCHMARK.json").write_bytes(
        (ROOT / "BENCHMARK.json").read_bytes())
    for p in harness.load_benchmark(ROOT)["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
