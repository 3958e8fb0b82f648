"""Every cell's load generator and reference comparison, run in-process at a tiny
size on the CPU: set-up, window, check and the result line's keys."""
import math
import pathlib
import time

import pytest

from bench import harness, loads, trace
from bench_cases import CELLS, CPU_PEAKS, cpu_device, run_tiny, tiny_cell

RECORDED = (pathlib.Path(__file__).resolve().parent / "data"
            / "tiny_als.trace.json.gz")


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == names and "setup_s" in names
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_every_per_layer_metric(name, monkeypatch):
    """A ``--trace 1`` run's line: every per-layer metric of the cell, the
    device's busy and window seconds, and the breakdown.  The CPU has no
    device plane to trace, so the profile reads the trace recorded on a
    TPU v5e that ``test_work_and_trace`` checks."""
    recorded = trace.summarize(trace.load(RECORDED))

    def start(self):
        self.started = self.active = True
        self.t_start = loads.clock()

    monkeypatch.setattr(harness.Profile, "start", start)
    monkeypatch.setattr(harness.Profile, "stop",
                        lambda self: setattr(self, "active", False))
    monkeypatch.setattr(harness.Profile, "summary", lambda self: recorded)
    cell = tiny_cell(name)
    out = harness.run_cell(cell, 2**31 + 9, 1.0, True, CPU_PEAKS,
                           cpu_device(), time.perf_counter(),
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] >= 0
    # Set-up warmed every executable the window uses.
    assert all(v["value"] == 0 for k, v in out["metrics"].items()
               if k.startswith("compiles_in_window"))
    assert out["device"]["busy_s"] == recorded.busy_s
    assert out["device"]["window_s"] == recorded.window_s
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("name", ["chicago-als.segment", "windows-over"])
def test_traced_stretch_counts_the_work_that_ran_inside_it(name,
                                                           monkeypatch):
    """The traced stretch sits in the middle of the window, starts and
    stops between fits or batches, and its MTTKRP work is that of exactly
    the fits or requests that ran while it was on."""
    marks = []

    def start(self):
        self.started = self.active = True
        self.t_start = loads.clock()
        marks.append(self.t_start)

    def stop(self):
        self.active = False
        marks.append(loads.clock())

    monkeypatch.setattr(harness.Profile, "start", start)
    monkeypatch.setattr(harness.Profile, "stop", stop)
    cell = tiny_cell(name)
    drv = harness.make_load(cell, 2**31 + 13)
    drv.setup()
    seconds, profile = 2.0, harness.Profile(0.5)
    t0 = loads.clock()
    win = drv.window(seconds, profile)
    assert len(marks) == 2 and marks[0] - t0 >= 0.7
    assert win.traced_work.operations > 0
    if name.startswith("chicago"):
        per_fit = loads.work.sweep_work(drv.tensor.shape, drv.tensor.nnz,
                                        drv.rank).scaled(drv.n_iters)
        fits = win.traced_work.operations / per_fit.operations
        assert fits == round(fits) and 1 <= fits < len(drv.results)
    else:
        assert win.traced_work.operations < sum(
            loads.work.sweep_work(drv.pool[f][j].shape, drv.pool[f][j].nnz,
                                  drv.rank).scaled(drv.n_iters).operations
            for f, j in drv.reqs)
