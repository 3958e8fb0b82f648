"""Device-resident fused ALS engine vs the host loop: trajectory
equivalence, sync counting, executable-cache reuse, engine delegation."""
import numpy as np
import pytest

from repro.core import (cpd_als, cpd_als_fused, random_sparse,
                        sweep_cache_stats)


@pytest.mark.parametrize("shape,nnz,R", [
    ((25, 18, 12), 800, 4),            # 3-mode
    ((16, 12, 10, 8), 600, 5),         # 4-mode
])
@pytest.mark.parametrize("backend", ["segment", "pallas", "coo"])
def test_fused_matches_host_trajectory(shape, nnz, R, backend):
    """Same seed => fused (f32 on-device solve) and host (f64 numpy solve)
    produce the same fit trajectory to 1e-4."""
    t = random_sparse(shape, nnz, seed=2, distribution="powerlaw")
    host = cpd_als(t, rank=R, n_iters=4, kappa=4, tol=-1.0,
                   backend=backend, engine="host")
    fused = cpd_als_fused(t, rank=R, n_iters=4, kappa=4, tol=-1.0,
                          backend=backend)
    assert host.engine == "host" and fused.engine == "fused"
    np.testing.assert_allclose(fused.fits, host.fits, rtol=1e-4, atol=1e-4)
    for Fh, Ff in zip(host.factors, fused.factors):
        assert Fh.shape == Ff.shape


def test_fused_host_sync_budget():
    """<= 1 host sync per check_every iterations (+1 final materialization)."""
    t = random_sparse((30, 20, 15), 1000, seed=3, distribution="powerlaw")
    res = cpd_als_fused(t, rank=4, n_iters=8, kappa=4, tol=-1.0,
                        check_every=4)
    assert res.iters == 8
    assert res.host_syncs <= 8 // 4 + 1
    # host loop for the same run syncs every mode of every iteration
    host = cpd_als(t, rank=4, n_iters=8, kappa=4, tol=-1.0, engine="host")
    assert host.host_syncs >= 8 * t.nmodes


def test_fused_sweep_cache_reused_across_same_shape_tensors():
    """Second decomposition of a same-shape tensor must not rebuild the
    jitted sweep (zero retrace for the serving scenario)."""
    t1 = random_sparse((22, 14, 9), 500, seed=4)
    t2 = random_sparse((22, 14, 9), 500, seed=5)
    cpd_als_fused(t1, rank=3, n_iters=2, kappa=2, tol=-1.0)
    before = sweep_cache_stats()
    cpd_als_fused(t2, rank=3, n_iters=2, kappa=2, tol=-1.0)
    after = sweep_cache_stats()
    assert after["currsize"] == before["currsize"]
    assert after["hits"] == before["hits"] + 1


def test_cpd_als_delegates_to_fused_by_default():
    t = random_sparse((20, 12, 8), 400, seed=6)
    res = cpd_als(t, rank=3, n_iters=3, kappa=2, tol=-1.0)
    assert res.engine == "fused"
    # custom mttkrp_fn forces the host loop
    from repro.core import make_plan, mttkrp

    res2 = cpd_als(t, rank=3, n_iters=3, kappa=2, tol=-1.0,
                   mttkrp_fn=lambda plan, factors, mode: mttkrp(
                       plan, factors, mode, backend="segment"))
    assert res2.engine == "host"
    np.testing.assert_allclose(res.fits, res2.fits, rtol=1e-4, atol=1e-4)


def test_fused_convergence_break_matches_host():
    """With check_every=1 the fused engine stops at the same iteration."""
    t = random_sparse((18, 14, 10), 600, seed=7)
    host = cpd_als(t, rank=3, n_iters=20, kappa=2, tol=1e-4, engine="host")
    fused = cpd_als_fused(t, rank=3, n_iters=20, kappa=2, tol=1e-4,
                          check_every=1)
    assert abs(host.iters - fused.iters) <= 1   # f32-vs-f64 fit jitter
    np.testing.assert_allclose(fused.fits[-1], host.fits[-1], atol=1e-3)


@pytest.mark.parametrize("mode,engine_name", [("sequential", "fused"),
                                              ("batched", "batched")])
def test_als_runner_serves_repeated_requests(mode, engine_name):
    """Runtime integration: ALSRunner routes through the fused engine
    (sequential) or the vmapped service (batched) and records per-request
    latency/sync/cache stats."""
    from repro.runtime import ALSRunner

    runner = ALSRunner(rank=3, kappa=2, check_every=2, mode=mode)
    for seed in (0, 1, 2):
        t = random_sparse((20, 12, 8), 400, seed=seed)
        res = runner.decompose(t, n_iters=4, tol=-1.0)
        assert res.engine == engine_name
    assert len(runner.history) == 3
    assert all(r["host_syncs"] <= 4 // 2 + 1 for r in runner.history)
    # satellite: per-request executable-cache deltas distinguish retrace
    # stragglers from contention stragglers — first request compiles, the
    # same-shape repeats must hit the cache.
    assert runner.history[0]["sweep_cache_misses"] >= 1
    assert all(r["sweep_cache_misses"] == 0 for r in runner.history[1:])
    assert all(r["sweep_cache_hits"] >= 1 for r in runner.history[1:])


def test_fused_scan_window_is_one_dispatch_per_block():
    """The check_every window runs as one lax.scan dispatch: host syncs are
    ceil(iters/k)+1 and the fit history still has one entry per sweep.
    With the tolerance off the windows fetch no fit, and the run syncs
    once, with the same fits."""
    from repro.obs.ledger import LEDGER

    t = random_sparse((24, 16, 10), 700, seed=9, distribution="powerlaw")
    # A tolerance above 0 that three sweeps of random data never meet.
    res = cpd_als_fused(t, rank=3, n_iters=7, kappa=2, tol=1e-12,
                        check_every=3)
    assert res.iters == 7
    assert len(res.fits) == 7              # 3 + 3 + 1 (remainder block)
    assert res.host_syncs == 3 + 1         # one per window + final
    LEDGER.reset()
    off = cpd_als_fused(t, rank=3, n_iters=7, kappa=2, tol=-1.0,
                        check_every=3)
    assert LEDGER.counts("sweep_block")["dispatches"] == 3
    assert off.host_syncs == 1             # the final read-back only
    assert off.fits == res.fits


def test_fused_exact_recovery():
    """The fused engine recovers an exactly low-rank tensor like the host."""
    import itertools

    from repro.core.coo import SparseTensor

    rng = np.random.default_rng(0)
    shape, R = (12, 10, 8), 3
    F = [rng.standard_normal((I, R)).astype(np.float32) for I in shape]
    dense = np.einsum("ir,jr,kr->ijk", *F)
    idx = np.array(list(itertools.product(*[range(s) for s in shape])),
                   dtype=np.int32)
    t = SparseTensor(idx, dense.reshape(-1).astype(np.float32), shape)
    res = cpd_als_fused(t, rank=R, n_iters=50, kappa=4, tol=1e-9)
    assert res.fits[-1] > 0.999


# FROSTT nips's mode structure at a CPU test's size: three modes taller
# than GATHER_CHUNK and a 17-row one, so every input of the last mode is
# gathered in HBM and its kernel has no one-hot input.
NIPS_LIKE = (600, 700, 1100, 17)


def test_fused_pallas_matches_host_loop_on_a_nips_shaped_tensor():
    """The fused Pallas sweep, with the HBM gather in every mode, against
    the host loop over the segment MTTKRP with float64 solves, and the
    bytes it counts as gathered in HBM against the hand count."""
    from repro.core import make_plan
    from repro.kernels.mttkrp_pallas import GATHER_CHUNK
    from repro.obs.ledger import LEDGER

    t = random_sparse(NIPS_LIKE, 6000, seed=16, distribution="powerlaw")
    plan = make_plan(t, 1)
    R, iters = 8, 3
    host = cpd_als(t, rank=R, n_iters=iters, tol=-1.0, backend="segment",
                   engine="host")
    LEDGER.reset()
    fused = cpd_als(t, rank=R, plan=plan, n_iters=iters, tol=-1.0,
                    backend="pallas")
    assert fused.engine == "fused" and fused.iters == iters
    # The gaps are the float32 on-device solves' against float64 ones
    # (the MTTKRPs agree to float32 rounding); each bound is about 20x
    # what this seed reads.  Fits: 5e-8 of fits near 1e-3.
    np.testing.assert_allclose(fused.fits, host.fits, rtol=0, atol=1e-6)
    # Factors: unit-norm columns, so an absolute gap; reads 3e-6.
    for Fh, Ff in zip(host.factors, fused.factors):
        np.testing.assert_allclose(Ff, Fh, rtol=0, atol=5e-5)
    # Column weights, near 2: reads 2e-6 relative.
    np.testing.assert_allclose(fused.weights, host.weights, rtol=5e-5)
    # Per sweep: each input taller than GATHER_CHUNK rows, as (G*T, R)
    # float32 rows, in every mode.
    per_sweep = 0
    for d in range(t.nmodes):
        p = plan.packed(d)
        tall = sum(NIPS_LIKE[w] > GATHER_CHUNK
                   for w in plan.layouts[d].input_modes())
        assert tall == (3 if d == 3 else 2)
        per_sweep += tall * p.num_slabs * p.tile * R * 4
    got = LEDGER.counts("sweep_block")["hbm_gather_bytes"]
    assert got == iters * per_sweep
