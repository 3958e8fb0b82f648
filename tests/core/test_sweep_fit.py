"""The CP sweep's fit reads <X, X_hat> from the last mode's MTTKRP output;
it must equal the fit that gathers every factor at every nonzero, on the
same state and fit data, on every backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import als_device, make_plan, random_sparse
from repro.core.mttkrp import collect_mode_data
from repro.methods import get_method
from repro.obs.ledger import LEDGER


def _inputs(t, backend, rank, method="cp"):
    plan = make_plan(t, 2)
    spec = get_method(method)
    mode_data, meta = collect_mode_data(plan, backend, rank,
                                        valued=spec.valued_mode_data)
    if spec.make_fit_data is not None:
        fit_data = spec.make_fit_data(t, None)
    else:
        fit_data = (jnp.asarray(t.indices),
                    jnp.asarray(t.values, jnp.float32),
                    jnp.asarray(t.norm() ** 2, jnp.float32))
    return mode_data, meta, fit_data


@pytest.mark.parametrize("shape,nnz,rank", [
    ((21, 15, 11), 700, 4),          # 3-mode
    ((14, 12, 9, 7), 600, 5),        # 4-mode
], ids=["3mode", "4mode"])
@pytest.mark.parametrize("backend", ["segment", "pallas", "coo"])
def test_cp_fit_from_mttkrp_equals_gathered_fit(shape, nnz, rank, backend):
    """After each of three sweeps from a seeded state, the sweep's fit
    equals ``_build_sparse_fit`` evaluated on the state it returns."""
    t = random_sparse(shape, nnz, seed=7, distribution="powerlaw")
    mode_data, meta, fit_data = _inputs(t, backend, rank)
    n = len(shape)
    sweep = jax.jit(als_device.build_sweep_fn(
        backend, n, rank, shape, meta, True, "inv"))
    gathered = jax.jit(als_device._build_sparse_fit(rank, None))
    state = als_device.init_state(shape, rank, 3)
    for _ in range(3):
        state, fit = sweep(state, mode_data, fit_data)
        want = gathered(state[0], state[1], state[2], fit_data)
        assert 0.0 < float(want) < 1.0
        np.testing.assert_allclose(float(fit), float(want), rtol=0,
                                   atol=1e-6)


@pytest.mark.parametrize("method,path", [
    ("cp", "mttkrp"), ("nncp", "gather"), ("masked", "gather")])
def test_sweep_fit_counter_names_the_path(method, path):
    """Each trace of a sweep counts how its fit reads <X, X_hat>: from
    the last MTTKRP (the inline CP sweep) or by the gather (methods)."""
    shape, rank = (12, 10, 8), 3
    t = random_sparse(shape, 300, seed=5)
    mode_data, meta, fit_data = _inputs(t, "segment", rank, method)
    sweep = als_device.build_sweep_fn(
        "segment", len(shape), rank, shape, meta, True, "inv",
        method=method)
    state = als_device.init_state(shape, rank, 0)
    LEDGER.reset()
    jax.eval_shape(lambda *a: sweep(*a), state, mode_data, fit_data)
    c = LEDGER.counts("sweep_fit")
    other = {"mttkrp": "gather", "gather": "mttkrp"}[path]
    assert (c.get(path, 0), c.get(other, 0)) == (1, 0)
