"""Each fault the cells can have, planted in the program underneath a
whole run at a size a CPU test holds, makes ``correct`` come out false:
an ALS sweep that returns its state unchanged, half of a service batch
answered with the other half's results, and a decomposition altered where
it is produced (one component's weight lost)."""
import numpy as np
import pytest

from bench_cases import CELLS, run_tiny, tiny_cell


@pytest.fixture
def fresh_executables():
    """Planted faults must not reach executables cached for other tests."""
    from repro.core import als_device
    from repro.serve import batched_engine

    caches = (als_device.build_sweep_fn, als_device._build_sweep_block,
              batched_engine._build_batched_block)

    def clear():
        for cache in caches:
            cache.cache_clear()

    clear()
    yield
    clear()


def _state_unchanged(monkeypatch):
    from repro.core import als_device

    real = als_device.build_sweep_fn

    def build(*args, **kw):
        sweep = real(*args, **kw)

        def frozen(state, mode_data_all, fit_data):
            _, fit = sweep(state, mode_data_all, fit_data)
            return state, fit
        return frozen

    monkeypatch.setattr(als_device, "build_sweep_fn", build)


def _half_batch(monkeypatch):
    from repro.serve.batched_engine import BatchedEngine

    real = BatchedEngine._materialize

    def materialize(self, *args, **kw):
        out = real(self, *args, **kw)
        half = len(out) // 2
        return out[:len(out) - half] + out[:half]

    monkeypatch.setattr(BatchedEngine, "_materialize", materialize)


def _drop_weight(result):
    result.weights = np.array(result.weights, copy=True)
    result.weights[-1] = 0.0
    return result


def _answer_altered(monkeypatch):
    from repro.core import als_device
    from repro.serve.batched_engine import BatchedEngine

    fused = als_device.cpd_als_fused
    monkeypatch.setattr(als_device, "cpd_als_fused",
                        lambda *a, **kw: _drop_weight(fused(*a, **kw)))
    real = BatchedEngine._materialize
    monkeypatch.setattr(
        BatchedEngine, "_materialize",
        lambda self, *a, **kw: [_drop_weight(r)
                                for r in real(self, *a, **kw)])


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
CASES = [(c, f) for c in CELLS for f in FAULTS
         if not (f == "half_batch" and c.startswith("chicago"))]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_refused(name, fault, monkeypatch,
                                  fresh_executables):
    cell = tiny_cell(name)
    if fault == "half_batch":
        # Arrivals close enough together that batches fill.
        cell.traffic["rate_per_s"] = 400.0
    FAULTS[fault](monkeypatch)
    out = run_tiny(cell, seconds=0.5)
    assert not out["correct"], out["checks"]
