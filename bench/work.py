"""The work an MTTKRP needs, counted from shapes, and the chip's least time.

The count is of what the algorithm needs, whatever implements it: the
Pallas kernel's one-hot MXU gathers and the segment path's ``(nnz, R)``
intermediates are both implementation, and neither adds to it.  For one
MTTKRP of mode ``n`` of an ``N``-mode tensor with ``nnz`` nonzeros, rank
``R`` and mode sizes ``I_w``:

* operations = ``nnz * R * N``: per nonzero and column, ``N - 1``
  multiplies (the value times ``N - 1`` factor entries) and one add into
  the output row;
* bytes = ``nnz * (4 * N + 4)`` (int32 coordinates and the float32 value,
  read once) + ``4 * R * sum_{w != n} I_w`` (the input factors, read once)
  + ``4 * R * I_n`` (the output, written once).

The least time is the larger of operations over the peak operation rate
and bytes over the peak memory bandwidth; ``bound`` names which.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

_F32 = 4
_I32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    operations: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.operations + other.operations,
                    self.bytes + other.bytes)

    def scaled(self, k: float) -> "Work":
        return Work(self.operations * k, self.bytes * k)


ZERO = Work(0.0, 0.0)


def mttkrp_work(shape, nnz: int, rank: int, mode: int) -> Work:
    """Operations and bytes of one mode-``mode`` MTTKRP (module docstring)."""
    shape = [int(s) for s in shape]
    n = len(shape)
    if not 0 <= mode < n:
        raise ValueError(f"mode {mode} out of range for {n} modes")
    ops = float(nnz) * rank * n
    inputs = sum(s for w, s in enumerate(shape) if w != mode)
    nbytes = (float(nnz) * (_I32 * n + _F32) + _F32 * rank * inputs
              + _F32 * rank * shape[mode])
    return Work(ops, nbytes)


def sweep_work(shape, nnz: int, rank: int) -> Work:
    """One ALS sweep: an MTTKRP of every mode."""
    total = ZERO
    for mode in range(len(shape)):
        total = total + mttkrp_work(shape, nnz, rank, mode)
    return total


@dataclasses.dataclass(frozen=True)
class Peaks:
    device_kind: str
    flops_per_s: float
    hbm_bytes_per_s: float
    source: str


def load_peaks(device_kind: str, path=PEAKS_FILE) -> Peaks:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    row = table[device_kind]
    return Peaks(device_kind, float(row["flops_per_s"]),
                 float(row["hbm_bytes_per_s"]), row["source"])


def least_time(work: Work, peaks: Peaks) -> tuple[float, str]:
    """``(seconds, bound)``: the chip's least time for ``work`` and whether
    operations (``"flops"``) or bytes (``"hbm"``) bound it."""
    t_ops = work.operations / peaks.flops_per_s
    t_mem = work.bytes / peaks.hbm_bytes_per_s
    return (t_ops, "flops") if t_ops > t_mem else (t_mem, "hbm")
