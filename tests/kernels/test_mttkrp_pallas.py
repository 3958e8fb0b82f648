"""Pallas kernel vs the pure-jnp oracle: shape/dtype sweeps (interpret mode)."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import make_plan, mttkrp, plan_bucket, random_sparse
from repro.kernels import ops as kops
from repro.kernels.mttkrp_pallas import GATHER_CHUNK, mttkrp_pallas
from repro.kernels.ops import pack_slabs
from repro.obs.ledger import LEDGER

# Rows of a factor the kernel cannot hold resident: gathered in HBM.
TALL = 2 * GATHER_CHUNK + 77


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
    """The one-hot gather in the kernel (a factor of at most GATHER_CHUNK
    rows, resident in VMEM) and the HBM gather before it (a taller factor,
    its rows streamed in packed slot order) both match the packed oracle;
    mode 0 takes both paths in one call."""
    t = random_sparse((40, 2 * GATHER_CHUNK + 77, 9), 700, seed=7)
    factors = _factors(t.shape, 8, seed=8)
    plan = make_plan(t, kappa=2, block_rows=8, tile=128)
    for mode in (0, 1):
        packed = plan.packed(mode)
        in_f = [factors[w] for w in plan.layouts[mode].input_modes()]
        a = np.asarray(kops.mttkrp_packed(packed, in_f))
        b = np.asarray(kops.mttkrp_packed_ref(packed, in_f))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _packed_pair(plan, mode, factors, **kw):
    """(kernel, packed oracle) outputs of one mode."""
    packed = plan.packed(mode)
    in_f = [factors[w] for w in plan.layouts[mode].input_modes()]
    return (np.asarray(kops.mttkrp_packed(packed, in_f, **kw)),
            np.asarray(kops.mttkrp_packed_ref(packed, in_f)))


@pytest.mark.parametrize("shape,tall", [
    ((9, TALL, 7, 5), 1),          # first of mode 0's inputs
    ((9, 7, TALL, 5), 1),          # middle
    ((9, 7, 5, TALL), 1),          # last
    ((9, 600, 530, 520), 3),       # every input
], ids=["first", "middle", "last", "all"])
def test_hbm_gather_in_every_position(shape, tall):
    """A factor taller than GATHER_CHUNK is gathered in HBM wherever it
    sits among the inputs, and the product keeps the oracle's order."""
    t = random_sparse(shape, 600, seed=31, distribution="powerlaw")
    factors = _factors(shape, 8, seed=32)
    plan = make_plan(t, kappa=2, block_rows=8, tile=128)
    LEDGER.reset()
    a, b = _packed_pair(plan, 0, factors)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    c = LEDGER.counts("pallas_gather")
    assert (c["hbm"], c["onehot"]) == (tall, 3 - tall)


def test_hbm_gather_rank_tiled():
    """Rank tiling streams the gathered rows one (tile, rank_block) block
    at a time: bit-identical to the whole rank, and the oracle's answer."""
    t = random_sparse((40, TALL, 9), 700, seed=33, distribution="powerlaw")
    factors = _factors(t.shape, 40, seed=34)
    plan = make_plan(t, kappa=2, block_rows=8, tile=128)
    for mode in (0, 2):
        blocked, ref = _packed_pair(plan, mode, factors, rank_block=16)
        full, _ = _packed_pair(plan, mode, factors)
        np.testing.assert_array_equal(blocked, full)
        np.testing.assert_allclose(blocked, ref, rtol=1e-5, atol=1e-5)


def test_hbm_gather_vmapped():
    """The serving path vmaps the kernel over bucket-mates: with a tall
    factor, a batch of three gives each member bit for bit what a batch
    of one gives it, and the oracle's answer."""
    shape, rank, mode = (9, TALL, 7), 8, 0
    part = plan_bucket(shape, 512, rank)
    mp = part.modes[mode]
    plans = [make_plan(random_sparse(shape, 400 + 30 * i, seed=40 + i),
                       kappa=1, partition=part) for i in range(3)]
    facs = [_factors(shape, rank, seed=50 + i) for i in range(3)]
    in_modes = plans[0].layouts[mode].input_modes()

    def one(rb_of, first, idx, vals, lrows, *fs):
        return mttkrp_pallas(rb_of, first, idx, vals, lrows, list(fs),
                             num_row_blocks=mp.num_row_blocks,
                             block_rows=mp.block_rows, tile=mp.tile,
                             rank_block=mp.rank_block)

    def batch(members):
        cols = []
        for i in members:
            p = plans[i].packed(mode)
            cols.append([p.rb_of, p.first, p.idx_packed, p.vals_packed,
                         p.lrows_packed] + [facs[i][w] for w in in_modes])
        return [jnp.stack([jnp.asarray(a) for a in col])
                for col in zip(*cols)]

    b3 = np.asarray(jax.vmap(one)(*batch([0, 1, 2])))
    for i in range(3):
        b1 = np.asarray(jax.vmap(one)(*batch([i])))[0]
        np.testing.assert_array_equal(b3[i], b1)
        ref = np.asarray(kops.mttkrp_packed_ref(
            plans[i].packed(mode), [facs[i][w] for w in in_modes]))
        np.testing.assert_allclose(b3[i][:shape[mode]], ref, rtol=1e-5,
                                   atol=1e-6)


def test_hbm_gather_masked_valued():
    """The masked method's valued MTTKRP scatters fresh values into the
    slabs and runs the same kernel: with a tall factor it matches the
    oracle on the slabs holding those values."""
    from repro.core import als_device

    t = random_sparse((40, TALL, 9), 700, seed=61, distribution="powerlaw")
    rank = 8
    factors = _factors(t.shape, rank, seed=62)
    plan = make_plan(t, kappa=2, block_rows=8, tile=128)
    mode_data, metas = als_device.collect_structural_mode_data(
        plan, "pallas", rank)
    valued = als_device._build_valued_mttkrp("pallas", t.nmodes, t.shape,
                                             metas, True, None)
    vals = np.random.default_rng(63).standard_normal(t.nnz).astype(
        np.float32)
    for mode in (0, 2):
        got = np.asarray(valued(mode, mode_data[mode], factors,
                                jnp.asarray(vals)))
        packed, lay = plan.packed(mode), plan.layouts[mode]
        vp = np.zeros_like(packed.vals_packed)
        vp[0, packed.val_scatter] = vals[lay.perm]
        rel = np.asarray(kops.mttkrp_packed_ref(
            dataclasses.replace(packed, vals_packed=vp),
            [factors[w] for w in lay.input_modes()]))
        want = np.zeros_like(rel)
        want[lay.row_perm] = rel
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode,hbm,onehot", [(0, 0, 3), (1, 1, 2)])
def test_pallas_gather_counter(mode, hbm, onehot):
    """Each trace of the kernel counts its inputs by gather: on a
    chicago-shaped tensor mode 0's factors (24, 77 and 32 rows) are all
    gathered in the kernel, and mode 1 gathers the 6,186-row factor in
    HBM."""
    shape = (6186, 24, 77, 32)
    plan = make_plan(random_sparse(shape, 500, seed=71), kappa=1)
    factors = _factors(shape, 32, seed=72)
    in_f = [factors[w] for w in plan.layouts[mode].input_modes()]
    LEDGER.reset()
    jax.eval_shape(lambda fs: kops.mttkrp_packed(plan.packed(mode), fs),
                   in_f)
    c = LEDGER.counts("pallas_gather")
    assert (c["hbm"], c["onehot"]) == (hbm, onehot)


@pytest.mark.parametrize("shape,talls", [
    ((6186, 24, 77, 32), (0, 1, 1, 1)),       # chicago: one tall factor
    ((2482, 2862, 14036, 17), (2, 2, 2, 3)),  # nips: mode 3 all tall
], ids=["chicago", "nips"])
def test_hbm_gather_bytes_by_hand(shape, talls):
    """Per mode, G*T*R_pad*4 bytes for each input taller than
    GATHER_CHUNK rows, the same inputs the kernel's trace gathers in HBM
    (the rest by one-hot matmuls); rank 40 in blocks of 16 pads to 48
    columns."""
    plan = make_plan(random_sparse(shape, 700, seed=73), kappa=1)
    for mode, tall in enumerate(talls):
        p = plan.packed(mode)
        rows = [shape[w] for w in plan.layouts[mode].input_modes()]
        slots = p.num_slabs * p.tile
        assert kops.hbm_gather_bytes(p, rows, 32) == tall * slots * 32 * 4
        assert kops.hbm_gather_bytes(p, rows, 32, 32) == tall * slots * 128
        assert kops.hbm_gather_bytes(p, rows, 40, 16) == tall * slots * 192
        assert kops.hbm_gather_bytes(p, rows, 40, 128) == tall * slots * 160
        LEDGER.reset()
        jax.eval_shape(lambda fs: kops.mttkrp_packed(p, fs),
                       [jnp.zeros((n, 32), jnp.float32) for n in rows])
        c = LEDGER.counts("pallas_gather")
        assert (c["hbm"], c["onehot"]) == (tall, 3 - tall)


def test_chicago_plans_unchanged_by_hbm_gather():
    """The VMEM model counts a tall factor as one streamed block, not as
    resident rows.  Every chicago-r32 candidate tiling fit the budget
    before, so the plans stay what they were: these literals are the
    plans of the tree that still gathered tall factors in the kernel."""
    from repro.core.plan import _UniformModeStats, plan_layout

    shape, nnz = (6186, 24, 77, 32), 5_330_673

    def powerlaw_stats(d):
        # chicago's row skew: the r-th hottest row has weight (r+1)^-0.5.
        s = _UniformModeStats(shape, d, nnz)
        p = (np.arange(shape[d]) + 1.0) ** -0.5
        cdf = np.concatenate([[0.0], np.cumsum(p / p.sum())])
        s.row_ptr = np.round(cdf * nnz).astype(np.int64)
        return s

    def tiling(plans):
        return [(m.block_rows, m.tile, m.rank_block) for m in plans]

    assert tiling(plan_layout(powerlaw_stats(d), 32) for d in range(4)) == [
        (128, 512, 32), (32, 512, 32), (128, 512, 32), (32, 512, 32)]
    # The tiling cpd_als packs with by default (block_rows 128, tile 256).
    assert tiling(plan_layout(powerlaw_stats(d), 32, block_rows=128,
                              tile=256) for d in range(4)) == [
        (128, 256, 32)] * 4
    assert tiling(plan_bucket(shape, 5_330_688, 32).modes) == [
        (32, 512, 32), (32, 512, 32), (32, 512, 32), (8, 512, 32)]


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
    assert kops.auto_rank_block(64, 128, 256, [100, 100]) == 64
    # Factors gathered in HBM hold one streamed block in VMEM, not their
    # rows: rank 256 over two 20k-row factors stays whole.
    assert kops.auto_rank_block(256, 128, 256, [20_000, 20_000]) == 256
    # Rank 8192 over two resident 500-row factors overflows: the widest
    # 128-column multiple that fits is chosen.
    frows = [500, 500]
    rb = kops.auto_rank_block(8192, 128, 256, frows)
    assert rb % 128 == 0 and rb < 8192
    assert (kops.kernel_vmem_bytes(128, 256, rb, frows) <= kops._VMEM_BYTES
            < kops.kernel_vmem_bytes(128, 256, rb + 128, frows))
    # A rank below 128 lanes is never split: R itself or an error.
    frows = [32, 16]
    budget = kops.kernel_vmem_bytes(16, 128, 32, frows)
    assert kops.auto_rank_block(32, 16, 128, frows,
                                vmem_budget=budget) == 32
    with pytest.raises(ValueError, match="mode 0"):
        kops.auto_rank_block(32, 16, 128, frows, vmem_budget=budget - 1,
                             mode=0)
    # estimate_pack_cost reports the tiling and scales cost by the passes.
    t = random_sparse((64, 32, 16), 800, seed=23)
    plan = make_plan(t, kappa=2, block_rows=16, tile=128)
    lay = plan.layouts[0]
    small = kops.estimate_pack_cost(
        lay, 16, 128, 256, frows,
        vmem_budget=kops.kernel_vmem_bytes(16, 128, 128, frows))
    big = kops.estimate_pack_cost(lay, 16, 128, 256, frows)
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
                rb = kops.auto_rank_block(rank, 128, 256, [rows // 3] * 3)
            except ValueError:
                continue
            assert rb == rank or rb % 128 == 0


def test_planner_raises_when_factor_cannot_fit_vmem():
    """A mode whose VMEM-resident input factors overflow VMEM at every
    tiling is an error naming the mode, those factors' rows and the
    budget, not a silent default tiling: 99 resident 512-row factors at
    rank 32.  Enron's 244,268-row mode, which overflowed while tall
    factors were resident, now plans: its tall factors stream."""
    with pytest.raises(ValueError) as e:
        plan_bucket((GATHER_CHUNK,) * 100, 1 << 20, 32)
    msg = str(e.value)
    assert "mode 0" in msg and f"{99 * GATHER_CHUNK} rows" in msg
    assert str(kops._VMEM_BYTES) in msg
    enron = plan_bucket((6_066, 5_699, 244_268, 1_176), 1 << 20, 32)
    assert [m.rank_block for m in enron.modes] == [32] * 4


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
        frows = [t.shape[w] for w in lay.input_modes()]
        br, tile = kops.auto_tiles(lay, rank=32, factor_rows=frows)
        auto = kops.estimate_pack_cost(lay, br, tile, 32, frows)
        dflt = kops.estimate_pack_cost(lay, kops.DEFAULT_BLOCK_ROWS,
                                       kops.DEFAULT_TILE, 32, frows)
        if dflt["vmem_ok"]:
            assert auto["cost"] <= dflt["cost"] + 1e-9
