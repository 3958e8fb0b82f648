"""MTTKRP engines vs the dense oracle + property tests (hypothesis)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property tests fall back to fixed examples
    HAVE_HYPOTHESIS = False

from repro.core import (Scheme, SparseTensor, als_device, low_rank_sparse,
                        make_plan, mttkrp, mttkrp_dense_ref, random_sparse)
from repro.core.mttkrp import collect_mode_data


def _factors(shape, R, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((I, R)).astype(np.float32))
            for I in shape]


@pytest.mark.parametrize("shape,nnz", [
    ((40, 30, 20), 500),
    ((64, 8, 8, 8), 700),           # 4-mode
    ((16, 16, 4, 8, 6), 400),       # 5-mode (beyond the baselines' 4)
    ((100, 3, 7), 250),             # modes smaller than kappa
])
@pytest.mark.parametrize("backend", ["segment", "coo", "pallas"])
def test_backends_match_dense(shape, nnz, backend):
    t = random_sparse(shape, nnz, seed=1, distribution="powerlaw")
    R = 8
    factors = _factors(shape, R)
    plan = make_plan(t, kappa=6)
    for d in range(t.nmodes):
        ref = mttkrp_dense_ref(t, [np.asarray(f) for f in factors], d)
        out = np.asarray(mttkrp(plan, factors, d, backend=backend))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("valued", [False, True], ids=["baked", "valued"])
@pytest.mark.parametrize("backend", ["segment", "pallas", "coo"])
def test_sweep_and_one_shot_share_the_dispatch(backend, valued):
    """The sweep's per-mode MTTKRP (value-baked mode data, or structural
    mode data fed fresh canonical-order values), ``core.mttkrp.mttkrp``
    and the dense oracle agree: one dispatch serves every caller."""
    t = random_sparse((30, 12, 7, 5), 600, seed=21, distribution="powerlaw")
    R = 8
    factors = _factors(t.shape, R, seed=22)
    vals = (np.random.default_rng(23).standard_normal(t.nnz).astype(
        np.float32) if valued else t.values)
    tv = SparseTensor(t.indices, vals, t.shape)
    mode_data, meta = collect_mode_data(make_plan(t, kappa=3), backend, R,
                                        valued=valued)
    sweep_mttkrp = jax.jit(als_device._build_mttkrp(
        backend, t.shape, meta, True, None), static_argnums=0)
    extra = (jnp.asarray(vals),) if valued else ()
    vplan = make_plan(tv, kappa=3)
    for d in range(t.nmodes):
        ref = mttkrp_dense_ref(tv, [np.asarray(f) for f in factors], d)
        swept = np.asarray(sweep_mttkrp(d, mode_data[d], factors, *extra))
        one_shot = np.asarray(mttkrp(vplan, factors, d, backend=backend))
        np.testing.assert_allclose(swept, ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(one_shot, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("scheme", [Scheme.INDEX_PARTITION, Scheme.NNZ_PARTITION])
def test_forced_schemes_agree(scheme):
    t = random_sparse((50, 9, 33), 800, seed=3, distribution="powerlaw")
    factors = _factors(t.shape, 16, seed=4)
    plan = make_plan(t, kappa=8, scheme=scheme)
    for d in range(3):
        ref = mttkrp_dense_ref(t, [np.asarray(f) for f in factors], d)
        out = np.asarray(mttkrp(plan, factors, d))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def _matches_dense_case(shape, nnz, kappa, R):
    """For arbitrary small tensors, every mode's MTTKRP equals the dense
    matricization @ Khatri-Rao product."""
    t = random_sparse(shape, min(nnz, int(np.prod(shape))), seed=7)
    factors = _factors(t.shape, R, seed=8)
    plan = make_plan(t, kappa=kappa)
    for d in range(t.nmodes):
        ref = mttkrp_dense_ref(t, [np.asarray(f) for f in factors], d)
        out = np.asarray(mttkrp(plan, factors, d))
        np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3)


def _linearity_case(mode, alpha, seed):
    """MTTKRP(alpha * X) == alpha * MTTKRP(X) (linearity in tensor values)."""
    t = random_sparse((20, 15, 10), 300, seed=seed % 97)
    from repro.core.coo import SparseTensor
    t2 = SparseTensor(t.indices, (alpha * t.values).astype(np.float32), t.shape)
    factors = _factors(t.shape, 4, seed=9)
    out1 = np.asarray(mttkrp(make_plan(t, 4), factors, mode))
    out2 = np.asarray(mttkrp(make_plan(t2, 4), factors, mode))
    np.testing.assert_allclose(out2, alpha * out1, rtol=1e-3, atol=1e-3)


def _permutation_invariance_case(seed):
    """The COO nnz ordering must not affect the result (the mode-specific
    layout re-sorts internally)."""
    t = random_sparse((25, 12, 18), 400, seed=11)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(t.nnz)
    tp = t.permuted(perm)
    factors = _factors(t.shape, 8, seed=12)
    for d in range(3):
        a = np.asarray(mttkrp(make_plan(t, 5), factors, d))
        b = np.asarray(mttkrp(make_plan(tp, 5), factors, d))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(
        st.integers(3, 4).flatmap(
            lambda n: st.tuples(*[st.integers(3, 24) for _ in range(n)])),
        st.integers(10, 200),
        st.integers(1, 12),
        st.integers(1, 6),
    )
    def test_property_matches_dense(shape, nnz, kappa, R):
        _matches_dense_case(shape, nnz, kappa, R)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2), st.floats(-2.0, 2.0), st.integers(0, 10_000))
    def test_property_linearity_in_values(mode, alpha, seed):
        _linearity_case(mode, alpha, seed)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_nnz_permutation_invariance(seed):
        _permutation_invariance_case(seed)
else:
    @pytest.mark.parametrize("shape,nnz,kappa,R", [
        ((5, 7, 9), 60, 3, 4), ((4, 4, 4, 4), 120, 6, 2),
        ((24, 3, 11), 200, 12, 6),
    ])
    def test_property_matches_dense(shape, nnz, kappa, R):
        _matches_dense_case(shape, nnz, kappa, R)

    @pytest.mark.parametrize("mode,alpha,seed",
                             [(0, 1.5, 0), (1, -2.0, 42), (2, 0.0, 7)])
    def test_property_linearity_in_values(mode, alpha, seed):
        _linearity_case(mode, alpha, seed)

    @pytest.mark.parametrize("seed", [0, 123, 9999])
    def test_property_nnz_permutation_invariance(seed):
        _permutation_invariance_case(seed)
