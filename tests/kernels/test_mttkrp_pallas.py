"""Pallas kernel vs the pure-jnp oracle: shape/dtype sweeps (interpret mode)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import make_plan, mttkrp, random_sparse
from repro.kernels import ops as kops
from repro.kernels.ops import pack_slabs


def _factors(shape, R, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((I, R)).astype(dtype))
            for I in shape]


@pytest.mark.parametrize("shape,nnz,R", [
    ((64, 32, 16), 1000, 8),
    ((128, 8, 8), 600, 32),
    ((32, 32, 32, 8), 800, 16),       # 4-mode
    ((16, 8, 4, 4, 4), 300, 4),       # 5-mode
    ((257, 63, 5), 900, 33),          # non-aligned dims / rank
])
def test_kernel_matches_oracle_shapes(shape, nnz, R):
    t = random_sparse(shape, nnz, seed=1, distribution="powerlaw")
    factors = _factors(shape, R, seed=2)
    plan = make_plan(t, kappa=4, block_rows=16, tile=64)
    for d in range(t.nmodes):
        pal = np.asarray(mttkrp(plan, factors, d, backend="pallas"))
        seg = np.asarray(mttkrp(plan, factors, d, backend="segment"))
        np.testing.assert_allclose(pal, seg, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,rtol", [
    (np.float32, 1e-5),
    (jnp.bfloat16, 2e-2),
])
def test_kernel_dtypes(dtype, rtol):
    t = random_sparse((48, 24, 12), 700, seed=3)
    factors = _factors(t.shape, 16, seed=4, dtype=dtype)
    plan = make_plan(t, kappa=2, block_rows=8, tile=32)
    for d in range(3):
        pal = np.asarray(mttkrp(plan, factors, d, backend="pallas"))
        f32 = [f.astype(jnp.float32) for f in factors]
        ref = np.asarray(mttkrp(plan, f32, d, backend="segment"))
        np.testing.assert_allclose(pal, ref, rtol=rtol, atol=rtol)


@pytest.mark.parametrize("block_rows,tile", [(8, 32), (16, 128), (128, 256)])
def test_kernel_blockspec_sweep(block_rows, tile):
    t = random_sparse((100, 40, 20), 1200, seed=5, distribution="powerlaw")
    factors = _factors(t.shape, 8, seed=6)
    plan = make_plan(t, kappa=4, block_rows=block_rows, tile=tile)
    pal = np.asarray(mttkrp(plan, factors, 0, backend="pallas"))
    seg = np.asarray(mttkrp(plan, factors, 0, backend="segment"))
    np.testing.assert_allclose(pal, seg, rtol=1e-5, atol=1e-5)


def test_gather_paths_agree():
    """The single one-hot gather (factor <= GATHER_CHUNK rows) and the
    chunked gather (taller factors, zero-padded to a chunk multiple) both
    match the packed oracle; mode 0 takes both paths in one call."""
    from repro.kernels.mttkrp_pallas import GATHER_CHUNK

    t = random_sparse((40, 2 * GATHER_CHUNK + 77, 9), 700, seed=7)
    factors = _factors(t.shape, 8, seed=8)
    plan = make_plan(t, kappa=2, block_rows=8, tile=128)
    for mode in (0, 1):
        packed = plan.packed(mode)
        in_f = [factors[w] for w in plan.layouts[mode].input_modes()]
        a = np.asarray(kops.mttkrp_packed(packed, in_f))
        b = np.asarray(kops.mttkrp_packed_ref(packed, in_f))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_packing_invariants():
    t = random_sparse((40, 10, 10), 500, seed=9, distribution="powerlaw")
    plan = make_plan(t, kappa=2, block_rows=8, tile=16)
    lay = plan.layouts[0]
    packed = plan.packed(0)
    # every row block has >= 1 slab; first flags are consistent
    assert packed.num_slabs >= packed.num_row_blocks
    firsts = np.flatnonzero(packed.first)
    assert len(firsts) == packed.num_row_blocks
    assert np.all(np.diff(packed.rb_of) >= 0)
    # padded values sum equals original values sum
    np.testing.assert_allclose(packed.vals_packed.sum(), lay.values.sum(),
                               rtol=1e-5)


def test_empty_row_blocks():
    """Rows with zero nnz must produce zero output rows, not garbage."""
    from repro.core.coo import SparseTensor
    idx = np.array([[0, 0, 0], [0, 1, 1], [63, 2, 2]], np.int32)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    t = SparseTensor(idx, vals, (64, 3, 3))
    factors = _factors(t.shape, 4, seed=10)
    plan = make_plan(t, kappa=1, block_rows=8, tile=8)
    pal = np.asarray(mttkrp(plan, factors, 0, backend="pallas"))
    seg = np.asarray(mttkrp(plan, factors, 0, backend="segment"))
    np.testing.assert_allclose(pal, seg, rtol=1e-5, atol=1e-6)
    assert np.all(pal[1:63] == 0)


def test_rank_blocked_kernel():
    """Rank tiling (grid (R_blocks, G)) is exact: bit-identical to the
    single-block kernel (columns are independent), and matches the packed
    oracle to f32 rounding, including when R does not divide rank_block."""
    t = random_sparse((96, 40, 24), 1500, seed=21, distribution="powerlaw")
    R = 40                      # rank_block=16 -> 3 blocks, padded to 48
    factors = _factors(t.shape, R, seed=22)
    plan = make_plan(t, kappa=4, block_rows=16, tile=64)
    for mode in range(t.nmodes):
        packed = plan.packed(mode)
        in_f = [factors[w] for w in plan.layouts[mode].input_modes()]
        blocked = np.asarray(kops.mttkrp_packed(packed, in_f, rank_block=16))
        full = np.asarray(kops.mttkrp_packed(packed, in_f))
        ref = np.asarray(kops.mttkrp_packed_ref(packed, in_f))
        np.testing.assert_array_equal(blocked, full)
        np.testing.assert_allclose(blocked, ref, rtol=1e-5, atol=1e-5)


def test_rank_block_forced_by_vmem_budget():
    """auto_rank_block tiles the rank in 128-column blocks when the whole
    rank overflows the budget, and the auto path through mttkrp_packed
    stays correct."""
    # Whole rank fits -> no tiling.
    assert kops.auto_rank_block(64, 128, 256, 200, 2) == 64
    # Rank 256 over 40k factor rows: only a 128-column block fits.
    assert kops.auto_rank_block(256, 128, 256, 40_000, 2) == 128
    # A rank below 128 lanes is never split: R itself or an error.
    budget = kops.kernel_vmem_bytes(16, 128, 32, 48, 2)
    assert kops.auto_rank_block(32, 16, 128, 48, 2,
                                vmem_budget=budget) == 32
    with pytest.raises(ValueError, match="mode 0"):
        kops.auto_rank_block(32, 16, 128, 48, 2, vmem_budget=budget - 1,
                             mode=0)
    # estimate_pack_cost reports the tiling and scales cost by the passes.
    t = random_sparse((64, 32, 16), 800, seed=23)
    plan = make_plan(t, kappa=2, block_rows=16, tile=128)
    lay = plan.layouts[0]
    small = kops.estimate_pack_cost(
        lay, 16, 128, 256, 48,
        vmem_budget=kops.kernel_vmem_bytes(16, 128, 128, 48, 2))
    big = kops.estimate_pack_cost(lay, 16, 128, 256, 48)
    assert small["num_rank_blocks"] > big["num_rank_blocks"] == 1
    assert small["vmem_ok"] and small["cost"] > big["cost"]
    # End-to-end through the mttkrp wrapper with an explicit small block
    # (interpret mode accepts any block width).
    factors = _factors(t.shape, 32, seed=24)
    a = np.asarray(mttkrp(plan, factors, 0, backend="pallas", rank_block=8))
    b = np.asarray(mttkrp(plan, factors, 0, backend="segment"))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_planner_only_offers_compilable_blocks():
    """Tiles are lane multiples, row blocks sublane multiples, and a rank
    block is R itself or a multiple of 128."""
    for br, tile in kops.tile_candidates():
        assert tile % 128 == 0 and br % 8 == 0
    for rank in (8, 32, 100, 128, 200, 384):
        for rows in (100, 10_000, 40_000, 80_000):
            try:
                rb = kops.auto_rank_block(rank, 128, 256, rows, 3)
            except ValueError:
                continue
            assert rb == rank or rb % 128 == 0


def test_planner_raises_when_factor_cannot_fit_vmem():
    """A mode whose input factors overflow VMEM at every tiling is an
    error naming the mode, its factor rows and the budget, not a silent
    default tiling (enron's 244,268-row mode at rank 32)."""
    from repro.core.plan import plan_bucket

    shape = (6_066, 5_699, 244_268, 1_176)
    with pytest.raises(ValueError) as e:
        plan_bucket(shape, 1 << 20, 32)
    msg = str(e.value)
    assert "mode 0" in msg and "251143 rows" in msg
    assert str(kops._VMEM_BYTES) in msg


def test_interpret_resolves_from_platform():
    """interpret=None interprets on the CPU backend (the test platform)
    and an explicit value passes through."""
    from repro.kernels.mttkrp_pallas import resolve_interpret

    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_auto_tiles_valid_and_correct():
    """auto_tiles picks a VMEM-feasible tiling; the kernel stays exact."""
    t = random_sparse((512, 64, 16), 3000, seed=11, distribution="powerlaw")
    plan0 = make_plan(t, kappa=4)
    for mode in range(3):
        lay = plan0.layouts[mode]
        br, tile = kops.auto_tiles(lay, rank=8)
        assert br in (8, 32, 128, 256) and tile in (128, 256, 512)
        plan = make_plan(t, kappa=4, block_rows=br, tile=tile)
        factors = _factors(t.shape, 8, seed=12)
        pal = np.asarray(mttkrp(plan, factors, mode, backend="pallas"))
        seg = np.asarray(mttkrp(plan, factors, mode, backend="segment"))
        np.testing.assert_allclose(pal, seg, rtol=1e-5, atol=1e-5)


def test_auto_tiles_never_worse_than_default_under_model():
    t = random_sparse((2000, 300, 10), 8000, seed=13, distribution="powerlaw")
    plan = make_plan(t, kappa=4)
    for mode in range(3):
        lay = plan.layouts[mode]
        frows = sum(t.shape[w] for w in lay.input_modes())
        br, tile = kops.auto_tiles(lay, rank=32, factor_rows=frows)
        auto = kops.estimate_pack_cost(lay, br, tile, 32, frows)
        dflt = kops.estimate_pack_cost(lay, kops.DEFAULT_BLOCK_ROWS,
                                       kops.DEFAULT_TILE, 32, frows)
        if dflt["vmem_ok"]:
            assert auto["cost"] <= dflt["cost"] + 1e-9
