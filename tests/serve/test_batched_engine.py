"""Vmapped batched engine: equivalence with per-tensor fused sweeps,
per-tensor convergence masking, executable-cache reuse."""
import numpy as np
import pytest

from repro.core import cpd_als_fused, make_plan, random_sparse
from repro.obs.ledger import LEDGER
from repro.serve import BatchedEngine, batched_cache_stats

# Three bucket shapes (incl. a 4-mode one) for the equivalence matrix.
BUCKETS = [
    ((18, 13, 9), 500, 3),
    ((10, 8, 6, 5), 350, 4),
    ((30, 7, 5), 420, 5),
]


def _stream(shape, nnz, n=3):
    return [random_sparse(shape, nnz - 13 * i, seed=i,
                          distribution="powerlaw") for i in range(n)]


@pytest.mark.parametrize("shape,nnz,R", BUCKETS)
def test_batched_matches_sequential_fused(shape, nnz, R):
    """One vmapped dispatch over B tensors == B independent fused runs
    (same seeds), to fp32 tolerance, on 3 bucket shapes."""
    ts = _stream(shape, nnz)
    eng = BatchedEngine(rank=R, kappa=2, backend="segment", check_every=2)
    batch = eng.decompose_batch(ts, n_iters=4, tol=-1.0,
                                seeds=[10, 11, 12], nnz_cap=nnz)
    for i, t in enumerate(ts):
        ref = cpd_als_fused(t, R, kappa=2, n_iters=4, tol=-1.0, seed=10 + i,
                            backend="segment", check_every=2)
        assert batch[i].engine == "batched" and batch[i].iters == ref.iters
        np.testing.assert_allclose(batch[i].fits, ref.fits,
                                   rtol=1e-5, atol=1e-5)
        for Fb, Fr in zip(batch[i].factors, ref.factors):
            np.testing.assert_allclose(Fb, Fr, rtol=1e-4, atol=1e-4)


def test_batched_coo_backend_matches_sequential():
    shape, nnz, R = BUCKETS[0]
    ts = _stream(shape, nnz)
    eng = BatchedEngine(rank=R, kappa=2, backend="coo", check_every=2)
    batch = eng.decompose_batch(ts, n_iters=3, tol=-1.0, seeds=[0, 1, 2],
                                nnz_cap=nnz)
    for i, t in enumerate(ts):
        ref = cpd_als_fused(t, R, kappa=2, n_iters=3, tol=-1.0, seed=i,
                            backend="coo")
        np.testing.assert_allclose(batch[i].fits, ref.fits,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["segment", "coo"])
def test_padded_bucket_cp_fit_matches_sequential(backend):
    """The vmapped CP sweep reads each tensor's fit from its last MTTKRP:
    zero-valued padding adds exactly nothing to it, so a bucket padded
    well past every tensor (one at under half the cap) reports the
    sequential fused fits, and each trace counts the MTTKRP-read fit."""
    shape, R, cap = (11, 9, 7, 5), 3, 400
    ts = [random_sparse(shape, n, seed=50 + i, distribution="powerlaw")
          for i, n in enumerate((370, 310, 150))]
    eng = BatchedEngine(rank=R, kappa=2, backend=backend, check_every=3)
    LEDGER.reset()
    batch = eng.decompose_batch(ts, n_iters=6, tol=-1.0, seeds=[5, 6, 7],
                                nnz_cap=cap)
    c = LEDGER.counts("sweep_fit")
    assert c.get("mttkrp", 0) >= 1 and c.get("gather", 0) == 0, c
    for i, t in enumerate(ts):
        ref = cpd_als_fused(t, R, kappa=2, n_iters=6, tol=-1.0, seed=5 + i,
                            backend=backend, check_every=3)
        assert batch[i].iters == ref.iters == 6
        np.testing.assert_allclose(batch[i].fits, ref.fits,
                                   rtol=1e-5, atol=1e-5)


def test_per_tensor_iteration_caps():
    """Requests batched together keep their own n_iters budget: a capped
    tensor's state freezes under the mask while bucket-mates sweep on."""
    ts = _stream((18, 13, 9), 480)
    eng = BatchedEngine(rank=3, kappa=2, backend="segment", check_every=2)
    batch = eng.decompose_batch(ts, n_iters=[2, 5, 3], tol=-1.0,
                                seeds=[0, 1, 2], nnz_cap=480)
    assert [r.iters for r in batch] == [2, 5, 3]
    assert [len(r.fits) for r in batch] == [2, 5, 3]
    # the frozen tensor's factors match a standalone 2-iteration run
    ref = cpd_als_fused(ts[0], 3, kappa=2, n_iters=2, tol=-1.0, seed=0,
                        backend="segment", check_every=2)
    for Fb, Fr in zip(batch[0].factors, ref.factors):
        np.testing.assert_allclose(Fb, Fr, rtol=1e-4, atol=1e-4)


def test_per_tensor_convergence_masking():
    """A converged tensor freezes (fit history stops) while the rest of
    the batch keeps iterating to their budget."""
    ts = _stream((18, 13, 9), 480, n=2)
    eng = BatchedEngine(rank=3, kappa=2, backend="segment", check_every=2)
    batch = eng.decompose_batch(ts, n_iters=6, tol=[1e9, -1.0],
                                seeds=[0, 1], nnz_cap=480)
    # Convergence is judged at window boundaries (the sequential rule):
    # the first boundary compares against -inf (never converges), so
    # tol=1e9 stops at the SECOND boundary, iteration 4.
    assert batch[0].iters == 4
    assert batch[1].iters == 6 and len(batch[1].fits) == 6


def test_convergence_stops_at_same_iteration_as_sequential():
    """For tol > 0 the batched mask must stop a tensor at exactly the
    iteration the sequential fused engine would stop at."""
    t = random_sparse((18, 13, 9), 480, seed=21, distribution="powerlaw")
    ref = cpd_als_fused(t, 3, kappa=2, n_iters=20, tol=1e-3, seed=4,
                        backend="segment", check_every=2)
    eng = BatchedEngine(rank=3, kappa=2, backend="segment", check_every=2)
    got = eng.decompose_batch([t, t], n_iters=20, tol=1e-3, seeds=[4, 4],
                              nnz_cap=480)[0]
    assert got.iters == ref.iters
    np.testing.assert_allclose(got.fits, ref.fits, rtol=1e-5, atol=1e-5)


def test_executable_cache_reused_across_batches():
    """Second batch of the same (bucket, B) class must not recompile."""
    eng = BatchedEngine(rank=3, kappa=2, backend="segment", check_every=2)
    ts1 = _stream((21, 11, 6), 300, n=2)
    ts2 = [random_sparse((21, 11, 6), 300 - 13 * i, seed=40 + i)
           for i in range(2)]
    eng.decompose_batch(ts1, n_iters=4, tol=-1.0, seeds=[0, 1], nnz_cap=320)
    before = batched_cache_stats()
    eng.decompose_batch(ts2, n_iters=4, tol=-1.0, seeds=[2, 3], nnz_cap=320)
    after = batched_cache_stats()
    assert after["currsize"] == before["currsize"]
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]


def test_batch_rejects_mixed_shapes():
    eng = BatchedEngine(rank=3)
    with pytest.raises(ValueError, match="mixes shapes"):
        eng.decompose_batch([random_sparse((10, 8, 6), 100, seed=0),
                             random_sparse((10, 8, 7), 100, seed=1)])


def test_batched_pallas_matches_sequential_fused():
    """The Pallas backend now stacks (core.plan slab caps): one vmapped
    dispatch over B tensors matches B sequential fused pallas runs under
    the SAME partition plan to fp32 tolerance."""
    shape, nnz, R = (18, 13, 9), 500, 3
    ts = _stream(shape, nnz)
    eng = BatchedEngine(rank=R, kappa=2, backend="pallas", check_every=2)
    cap = nnz
    batch = eng.decompose_batch(ts, n_iters=4, tol=-1.0,
                                seeds=[10, 11, 12], nnz_cap=cap)
    bplan = eng.bucket_plan(shape, cap)
    for i, t in enumerate(ts):
        mplan = make_plan(t, 2, partition=bplan)
        ref = cpd_als_fused(t, R, plan=mplan, kappa=2, n_iters=4, tol=-1.0,
                            seed=10 + i, backend="pallas", check_every=2)
        assert batch[i].iters == ref.iters
        np.testing.assert_allclose(batch[i].fits, ref.fits,
                                   rtol=1e-5, atol=1e-5)
        for Fb, Fr in zip(batch[i].factors, ref.factors):
            np.testing.assert_allclose(Fb, Fr, rtol=1e-4, atol=1e-4)


def test_batched_pallas_bit_identical_to_per_request():
    """Co-batching must never alter an individual pallas result: a B=3
    batch returns BIT-identical factors and weights to serving each
    request alone (B=1) through the same engine.  (The plain non-vmapped
    engine agrees only to fp32 tolerance — XLA lowers the R x R solve
    differently under batching — but batching itself is exact.)  The
    diagnostic fit scalar may drift in the last ulp between the two
    executables (different XLA fusion of the reduction), so it gets a
    tight tolerance rather than equality."""
    shape, nnz, R = (18, 13, 9), 500, 3
    ts = _stream(shape, nnz)
    eng = BatchedEngine(rank=R, kappa=2, backend="pallas", check_every=2)
    b3 = eng.decompose_batch(ts, n_iters=4, tol=-1.0, seeds=[10, 11, 12],
                             nnz_cap=512)
    for i, t in enumerate(ts):
        b1 = eng.decompose_batch([t], n_iters=4, tol=-1.0, seeds=[10 + i],
                                 nnz_cap=512)[0]
        for Fa, Fb in zip(b3[i].factors, b1.factors):
            assert np.array_equal(Fa, Fb)
        assert np.array_equal(b3[i].weights, b1.weights)
        np.testing.assert_allclose(b3[i].fits, b1.fits, rtol=0, atol=1e-6)


def test_empty_batch():
    assert BatchedEngine(rank=3).decompose_batch([]) == []


def test_zero_iteration_budget():
    """n_iters=0 returns the (normalized-init) state without crashing,
    matching the sequential engine's behavior."""
    t = random_sparse((10, 8, 6), 120, seed=0)
    res = BatchedEngine(rank=3).decompose_batch([t], n_iters=0,
                                                tol=-1.0, seeds=[0])[0]
    assert res.iters == 0 and res.fits == []
    assert [F.shape for F in res.factors] == [(10, 3), (8, 3), (6, 3)]
