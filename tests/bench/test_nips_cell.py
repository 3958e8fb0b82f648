"""The ``nips-als.pallas`` cell, shrunk for the CPU with FROSTT nips's mode
structure kept: three modes taller than ``GATHER_CHUNK`` rows and a 17-row
one, so that every input of mode 3 is gathered in HBM.  It runs plain and
traced like the cells of ``test_cells``, and ``hbm_gather_mb_per_iter.als``
reads the hand count of the bytes its plan gathers."""
import math
import time

import pytest

from bench import harness, loads, trace
from bench_cases import CPU_PEAKS, ROOT, cpu_device, run_tiny

# The trace recorded on a TPU v5e that test_work_and_trace checks.
RECORDED = ROOT / "tests" / "bench" / "data" / "tiny_als.trace.json.gz"

NAME = "nips-als.pallas"
METRIC = "hbm_gather_mb_per_iter.als"
SHAPE = [600, 700, 1100, 17]


def tiny_nips() -> harness.Cell:
    cell = harness.resolve(harness.load_benchmark(ROOT), NAME)
    cell.config.update(shape=SHAPE, nnz=6000, rank=8)
    cell.traffic.update(n_iters=3, reference_fits=2)
    assert cell.traffic["backend"] == "pallas"
    return cell


def test_nips_cell_runs_and_is_correct():
    cell = tiny_nips()
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"als_iter_s", "setup_s"} <= set(out["metrics"])
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0


def test_traced_nips_run_reports_every_per_layer_metric(monkeypatch):
    """On the trace recorded on a TPU v5e, as ``test_cells`` reads it."""
    recorded = trace.summarize(trace.load(RECORDED))

    def start(self):
        self.started = self.active = True
        self.t_start = loads.clock()

    monkeypatch.setattr(harness.Profile, "start", start)
    monkeypatch.setattr(harness.Profile, "stop",
                        lambda self: setattr(self, "active", False))
    monkeypatch.setattr(harness.Profile, "summary", lambda self: recorded)
    cell = tiny_nips()
    assert METRIC in {m["name"] for m in cell.per_layer}
    out = harness.run_cell(cell, 2**31 + 11, 1.0, True, CPU_PEAKS,
                           cpu_device(), time.perf_counter(),
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] >= 0
    assert out["metrics"]["compiles_in_window.als"]["value"] == 0
    assert out["metrics"][METRIC]["value"] > 0


def test_hbm_gather_mb_per_iter_equals_the_hand_count():
    """Per sweep, every input taller than ``GATHER_CHUNK`` rows of every
    mode, as ``(G*T, R)`` float32 rows in packed slot order: 2 inputs in
    modes 0-2 and 3 in mode 3."""
    from repro.kernels.mttkrp_pallas import GATHER_CHUNK

    drv = harness.make_load(tiny_nips(), 2**31 + 23)
    drv.setup()
    win = drv.window(0.5, None)
    assert win.counters["iters"] >= drv.n_iters
    plan, rank = drv.plan, drv.rank
    per_iter, talls = 0, []
    for d in range(len(SHAPE)):
        p = plan.packed(d)
        tall = sum(SHAPE[w] > GATHER_CHUNK
                   for w in plan.layouts[d].input_modes())
        talls.append(tall)
        per_iter += tall * p.num_slabs * p.tile * rank * 4
    assert talls == [2, 2, 2, 3]
    got = harness.load_reader(METRIC)(
        harness.Reading(win.counters, None, None, CPU_PEAKS))
    assert got == pytest.approx(per_iter / 1e6, rel=1e-12)


def test_hbm_gather_reader_gives_nothing_without_the_counter(monkeypatch):
    """A program that counts the bytes reads 0 where it gathered nothing;
    one without the count (``kernels.ops.hbm_gather_bytes``), or a window
    without iterations, gives nothing."""
    from repro.kernels import ops
    from repro.obs.ledger import LEDGER

    read = harness.load_reader(METRIC)
    reading = harness.Reading({"iters": 10, "fits": 1}, None, None,
                              CPU_PEAKS)
    LEDGER.reset()
    assert read(reading) == 0.0
    LEDGER.count("sweep_block", hbm_gather_bytes=3_000_000)
    assert read(reading) == pytest.approx(0.3)
    assert read(harness.Reading({"iters": 0}, None, None, CPU_PEAKS)) is None
    monkeypatch.delattr(ops, "hbm_gather_bytes")
    assert read(reading) is None
