"""Shrunken copies of the benchmark's cells, made here, that run
in-process on the CPU."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, work  # noqa: E402

CPU_PEAKS = work.Peaks("cpu", 1e12, 1e11, "a stand-in for tests")
CELLS = ("chicago-als.segment", "windows-steady", "windows-over",
         "chicago-als.pallas")


def tiny_cell(name: str) -> harness.Cell:
    """``name`` with its configuration and traffic cut to a CPU test's
    size; the limits are the cell's own."""
    cell = harness.resolve(harness.load_benchmark(ROOT, staged=True), name)
    if cell.traffic["load"] == "multistart_fits":
        cell.config.update(shape=[50, 24, 7, 5], nnz=3000, rank=8)
        cell.traffic.update(n_iters=4, reference_fits=2)
    else:
        fams = cell.config["families"]
        fams[0].update(shape=[6, 8, 7, 5], nnz=500)
        fams[1].update(shape=[4, 6, 30, 40], nnz=1200)
        cell.config.update(rank=8, pool_per_family=3)
        cell.config["service"]["max_batch"] = 2
        cell.traffic.update(rate_per_s=30.0, reference_requests=4)
    return cell


def cpu_device() -> dict:
    return {"platform": "cpu", "kind": "cpu", "count": 1,
            "peak_bytes": lambda: 0}


def run_tiny(cell: harness.Cell, seed: int = 2**31 + 7,
             seconds: float = 1.0, control: bool = False) -> dict:
    return harness.run_cell(cell, seed, seconds, False, CPU_PEAKS,
                            cpu_device(), time.perf_counter(),
                            control=control, log=lambda msg: None)
