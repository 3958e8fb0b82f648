"""The comparison that decides ``correct`` refuses the precision control
at a size a CPU test holds: the plain reference computed in bfloat16, put
in the program's place, its answers replacing what the timed path
returned."""
import dataclasses

import numpy as np
import pytest

from bench import harness, reference
from bench_cases import CELLS, tiny_cell


@dataclasses.dataclass
class _Answer:
    fits: list
    factors: list
    weights: np.ndarray
    iters: int


def _control_answer(tensor, rank, n_iters, seed, kernels_cls):
    kern = kernels_cls(tensor.indices, tensor.values, tensor.shape,
                       precision="bfloat16")
    norm_sq = float(np.sum(tensor.values.astype(np.float64) ** 2))
    fits, factors, weights = reference.cp_als(kern, norm_sq, rank, n_iters,
                                              seed)
    return _Answer(fits, factors, weights, n_iters)


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_refused(name):
    cell = tiny_cell(name)
    drv = harness.make_load(cell, seed=2**31 + 11)
    drv.setup()
    win = drv.window(1.0, None)
    drv.release()
    if isinstance(drv, harness.loads.MultistartFits):
        drv.results = drv.results[:3]
        drv.results = [
            (s, _control_answer(drv.tensor, drv.rank, drv.n_iters, s,
                                reference.DeviceKernels))
            for s, _ in drv.results]
    else:
        drv.results = [
            None if r is None else _control_answer(
                drv.pool[drv.reqs[k][0]][drv.reqs[k][1]], drv.rank,
                drv.n_iters, drv.seeds[k], reference.HostKernels)
            for k, r in enumerate(drv.results)]
    checks = drv.check(win)
    assert not all(c.ok for c in checks), [(c.name, c.value) for c in checks]
