"""The ledger's per-kind counters: what one fused fit dispatches, uploads
and spends preparing, against a hand count of the uploaded arrays; the
plan's cached uploads counted once; ``reset()`` zeroes them."""
import pytest

from repro.core import cpd_als, make_plan, random_sparse
from repro.obs.ledger import LEDGER, RetraceLedger
from repro.serve import BatchedEngine

SHAPE, NNZ, RANK = (10, 8, 6), 200, 4
N = len(SHAPE)


def _state_bytes() -> int:
    """Factors, grams and weights in float32."""
    return 4 * (RANK * sum(SHAPE) + N * RANK * RANK + RANK)


def _fit_data_bytes(nnz: int) -> int:
    """int32 coordinates, float32 values and the float32 squared norm."""
    return 4 * nnz * N + 4 * nnz + 4


def _segment_plan_bytes(nnz: int) -> int:
    """Per mode: input coordinates, rows, values and the row permutation."""
    return sum(4 * nnz * (N - 1) + 4 * nnz + 4 * nnz + 4 * i for i in SHAPE)


@pytest.mark.parametrize("backend", ["segment", "pallas"])
def test_fit_h2d_bytes_equal_the_hand_count(backend):
    t = random_sparse(SHAPE, NNZ, seed=5)
    plan = make_plan(t, 1)
    cpd_als(t, RANK, plan=plan, n_iters=1, tol=-1.0, backend=backend)
    LEDGER.reset()              # the plan's arrays are on the device now
    res = cpd_als(t, RANK, plan=plan, n_iters=4, tol=-1.0, check_every=2,
                  backend=backend)
    # The plan keeps every mode's data, the row permutation included, on
    # both backends: a fit uploads its state and fit data only.
    want = _state_bytes() + _fit_data_bytes(t.nnz)
    c = LEDGER.counts("sweep_block")
    assert c["h2d_bytes"] == want
    assert c["dispatches"] == 2
    assert res.host_syncs == 1      # tol < 0: no window fetches its fit
    assert c["prepare_s"] > 0.0
    assert LEDGER.counts("plan")["h2d_bytes"] == 0
    assert LEDGER.counts() == c


def test_plan_uploads_count_on_first_use_only():
    t = random_sparse(SHAPE, NNZ, seed=6)
    plan = make_plan(t, 1)
    cpd_als(t, RANK, plan=plan, n_iters=1, tol=-1.0)
    assert LEDGER.counts("plan")["h2d_bytes"] == _segment_plan_bytes(t.nnz)
    cpd_als(t, RANK, plan=plan, n_iters=1, tol=-1.0)
    assert LEDGER.counts("plan")["h2d_bytes"] == _segment_plan_bytes(t.nnz)
    assert LEDGER.counts("sweep_block")["h2d_bytes"] == 2 * (
        _state_bytes() + _fit_data_bytes(t.nnz))


def test_reset_zeroes_the_counters():
    led = RetraceLedger()
    led.count("k", dispatches=2, h2d_bytes=100, prepare_s=0.5)
    led.count("j", h2d_bytes=1)
    assert led.counts("k") == {"dispatches": 2, "h2d_bytes": 100,
                               "prepare_s": 0.5}
    assert led.counts()["h2d_bytes"] == 101
    led.reset()
    assert led.counts() == {"dispatches": 0, "h2d_bytes": 0,
                            "prepare_s": 0.0}


def test_a_kind_counts_names_of_its_own():
    led = RetraceLedger()
    led.count("g", hbm=1, onehot=2)
    led.count("g", hbm=1, onehot=2)
    led.count("k", dispatches=1)
    assert led.counts("g") == {"dispatches": 0, "h2d_bytes": 0,
                               "prepare_s": 0.0, "hbm": 2, "onehot": 4}
    assert led.counts() == {"dispatches": 1, "h2d_bytes": 0,
                            "prepare_s": 0.0}
    led.reset()
    assert led.counts("g") == {"dispatches": 0, "h2d_bytes": 0,
                               "prepare_s": 0.0}


def test_batched_engine_counts_its_batch():
    ts = [random_sparse(SHAPE, NNZ - 10 * i, seed=7 + i) for i in range(2)]
    eng = BatchedEngine(RANK, check_every=2)
    eng.decompose_batch(ts, n_iters=4, tol=-1.0, nnz_cap=NNZ)
    LEDGER.reset()
    prep = eng.prepare_batch(ts, n_iters=4, tol=-1.0, nnz_cap=NNZ)
    # Two padded requests: segment mode data, fit data, states, and the
    # per-request tolerance and iteration budget.
    want = 2 * (_segment_plan_bytes(NNZ) + _fit_data_bytes(NNZ)
                + _state_bytes()) + 2 * 4 + 2 * 4
    assert prep.h2d_bytes == want
    eng.execute_prepared(prep)
    c = LEDGER.counts("batched_block")
    assert c["h2d_bytes"] == want and c["dispatches"] == 2
    assert c["prepare_s"] > 0.0
