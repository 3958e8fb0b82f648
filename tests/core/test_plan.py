"""PartitionPlan: static caps bound every member of a bucket class, and
plan-padded execution is BIT-identical to unpadded across all backends."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax.numpy as jnp

from repro.core import (build_all_mode_layouts, make_plan, mttkrp,
                        plan_bucket, plan_layout, plan_tensor, quantize_nnz,
                        random_sparse, slab_cap)
from repro.kernels import ops as kops
from repro.serve.buckets import BucketPolicy, pad_tensor

SHAPE = (18, 13, 9)


def test_quantize_nnz_is_the_bucket_policy_rule():
    """BucketPolicy delegates to core.plan.quantize_nnz — one rule, two
    consumers, no possible disagreement."""
    p = BucketPolicy()
    for n in (1, 127, 128, 129, 700, 5000):
        assert p.nnz_cap(n) == quantize_nnz(n)
    g = BucketPolicy(mode="geometric", growth=1.5, min_cap=64)
    for n in (1, 65, 1000):
        assert g.nnz_cap(n) == quantize_nnz(n, mode="geometric",
                                            growth=1.5, min_cap=64)
    aligned = BucketPolicy.for_plan(256)
    assert aligned.nnz_cap(300) == 512      # lands on a slab boundary


def _assert_slab_cap_bounds(nnz, seed):
    """Any tensor with nnz <= nnz_cap packs within the plan's slab cap,
    for every mode, whatever its row distribution."""
    cap = quantize_nnz(nnz)
    t = random_sparse(SHAPE, nnz, seed=seed, distribution="powerlaw")
    plan = plan_bucket(SHAPE, cap, rank=3, kappa=2)
    for d, lay in enumerate(build_all_mode_layouts(t, 2)):
        mp = plan.modes[d]
        p = kops.pack_layout(lay, block_rows=mp.block_rows, tile=mp.tile)
        assert p.num_slabs <= mp.slab_cap, (d, p.num_slabs, mp.slab_cap)
        assert mp.slab_cap == slab_cap(lay.num_rows, cap, mp.block_rows,
                                       mp.tile)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(20, 520), st.integers(0, 7))
    def test_property_slab_cap_bounds_any_distribution(nnz, seed):
        _assert_slab_cap_bounds(nnz, seed)
else:
    @pytest.mark.parametrize("nnz,seed", [(20, 0), (333, 3), (512, 5)])
    def test_property_slab_cap_bounds_any_distribution(nnz, seed):
        _assert_slab_cap_bounds(nnz, seed)


def _factors(rng, shape, R):
    return [jnp.asarray(rng.standard_normal((I, R)).astype(np.float32))
            for I in shape]


def _mttkrp_padded_vs_unpadded(nnz, seed, backend):
    """The planning layer's padding is an exact no-op per backend:

      * pallas  — slab-cap padding (appended zero slabs) on the SAME
        unpadded layout: += 0.0 into an initialized block;
      * segment / coo — nnz padding (zero entries at the origin): +0.0
        into row 0's segment, stable sorts keep real-entry order.
    """
    R = 4
    t = random_sparse(SHAPE, nnz, seed=seed, distribution="powerlaw")
    cap = quantize_nnz(nnz)
    rng = np.random.default_rng(seed)
    factors = _factors(rng, SHAPE, R)
    bplan = plan_bucket(SHAPE, cap, rank=R, kappa=2)

    if backend == "pallas":
        for d, lay in enumerate(build_all_mode_layouts(t, 2)):
            mp = bplan.modes[d]
            in_f = [factors[w] for w in lay.input_modes()]
            raw = kops.pack_layout(lay, block_rows=mp.block_rows,
                                   tile=mp.tile)
            capped = kops.pack_layout(lay, block_rows=mp.block_rows,
                                      tile=mp.tile,
                                      num_slabs_cap=mp.slab_cap)
            assert capped.num_slabs == mp.slab_cap
            assert capped.num_real_slabs == raw.num_slabs
            a = np.asarray(kops.mttkrp_packed(raw, in_f,
                                              rank_block=mp.rank_block))
            b = np.asarray(kops.mttkrp_packed(capped, in_f,
                                              rank_block=mp.rank_block))
            assert np.array_equal(a, b), f"mode {d} not bit-identical"
        return

    plain = make_plan(t, 2)
    padded = make_plan(pad_tensor(t, cap), 2)
    for d in range(t.nmodes):
        a = np.asarray(mttkrp(plain, factors, d, backend=backend))
        b = np.asarray(mttkrp(padded, factors, d, backend=backend))
        assert np.array_equal(a, b), f"mode {d} not bit-identical"


if HAVE_HYPOTHESIS:
    @settings(max_examples=9, deadline=None)
    @given(st.sampled_from([170, 300, 450]), st.integers(0, 5),
           st.sampled_from(["segment", "pallas", "coo"]))
    def test_property_plan_padding_invariance(nnz, seed, backend):
        _mttkrp_padded_vs_unpadded(nnz, seed, backend)
else:
    @pytest.mark.parametrize("nnz,seed,backend",
                             [(170, 0, "segment"), (300, 2, "pallas"),
                              (450, 4, "coo"), (300, 1, "segment"),
                              (170, 3, "pallas")])
    def test_property_plan_padding_invariance(nnz, seed, backend):
        """Fixed-example fallback when hypothesis is unavailable."""
        _mttkrp_padded_vs_unpadded(nnz, seed, backend)


def test_vmapped_pallas_bit_identical_to_plain_kernel():
    """Stacked bucket-mates through jax.vmap == each tensor through the
    plain kernel, bit for bit (the property that makes the batched pallas
    backend exact)."""
    import jax

    R, cap = 4, 512
    ts = [random_sparse(SHAPE, 500 - 60 * i, seed=i,
                        distribution="powerlaw") for i in range(3)]
    bplan = plan_bucket(SHAPE, cap, rank=R, kappa=2)
    d = 0
    mp = bplan.modes[d]
    packs, perms = [], []
    for t in ts:
        lay = build_all_mode_layouts(t, 2)[d]
        packs.append(kops.pack_layout(lay, block_rows=mp.block_rows,
                                      tile=mp.tile,
                                      num_slabs_cap=mp.slab_cap))
        perms.append(lay.row_perm)
    rng = np.random.default_rng(0)
    facs = [jnp.asarray(np.stack(
        [rng.standard_normal((I, R)).astype(np.float32) for _ in ts]))
        for I in (SHAPE[1], SHAPE[2])]

    def one(rb, first, idx, vals, lrows, f1, f2):
        from repro.kernels.mttkrp_pallas import mttkrp_pallas
        return mttkrp_pallas(rb, first, idx, vals, lrows, [f1, f2],
                             num_row_blocks=mp.num_row_blocks,
                             block_rows=mp.block_rows, tile=mp.tile,
                             rank_block=mp.rank_block, interpret=True)

    stacked = [jnp.asarray(np.stack([getattr(p, f) for p in packs]))
               for f in ("rb_of", "first", "idx_packed", "vals_packed",
                         "lrows_packed")]
    out = jax.vmap(one)(*stacked, facs[0], facs[1])
    for i, p in enumerate(packs):
        seq = kops.mttkrp_packed(p, [facs[0][i], facs[1][i]],
                                 rank_block=mp.rank_block)
        assert np.array_equal(np.asarray(out[i][: p.num_rows]),
                              np.asarray(seq))


def test_plan_tensor_agrees_with_bucket():
    """A lone tensor's plan is its bucket class's plan (same quantizer)."""
    t = random_sparse(SHAPE, 300, seed=1)
    assert plan_tensor(t, rank=3, kappa=2) is plan_bucket(
        SHAPE, quantize_nnz(300), 3, 2)      # lru-cached identity


def test_plan_layout_pins_to_actual_packing():
    t = random_sparse(SHAPE, 400, seed=2)
    lay = build_all_mode_layouts(t, 2)[1]
    mp = plan_layout(lay, rank=5, block_rows=8, tile=64)
    assert (mp.block_rows, mp.tile) == (8, 64)
    assert mp.num_row_blocks == -(-lay.num_rows // 8)
    assert 1 <= mp.rank_block <= 5
    p = kops.pack_layout(lay, block_rows=8, tile=64)
    assert p.num_slabs <= mp.slab_cap


def test_pack_rejects_overflowing_cap():
    t = random_sparse(SHAPE, 400, seed=3)
    lay = build_all_mode_layouts(t, 2)[0]
    with pytest.raises(ValueError, match="slab"):
        kops.pack_layout(lay, block_rows=8, tile=64, num_slabs_cap=1)


# ---------------------------------------------------------------------------
# Pod plans + density-driven segment partitioning
# ---------------------------------------------------------------------------


def test_pod_plan_dispatch_arithmetic():
    """Batch is rounded up to the quantum FIRST, then to a mesh multiple,
    and the per-device sub-batch divides exactly."""
    from repro.core.plan import PodPlan

    pp = PodPlan(num_devices=8, batch_quantum=3)
    assert pp.dispatch_batch(1) == (8, 1)     # 1 -> 3 (quantum) -> 8 (mesh)
    assert pp.dispatch_batch(8) == (16, 2)    # 8 -> 9 -> 16
    assert pp.dispatch_batch(13) == (16, 2)
    assert pp.dispatch_batch(16) == (24, 3)   # 16 -> 18 -> 24
    for b in (1, 5, 8, 13, 16, 40):
        tot, per = pp.dispatch_batch(b)
        # Mesh divisibility is the hard invariant (shard_map slices
        # exactly); the quantum is only a lower-bound rounding step, so
        # the final total need not be a quantum multiple.
        assert tot >= b and tot == per * 8
    with pytest.raises(ValueError):
        pp.dispatch_batch(0)


def test_pod_sizing_needs_no_kernel_tiling():
    """Pod batch sizing plans no kernel tiling, so a segment pod engine
    sizes a bucket whose VMEM-resident factors exceed the kernel's VMEM
    budget (99 resident 512-row factors per mode; enron's tall modes now
    stream from an HBM gather and plan); the bucket's own kernel plan is
    an error naming the mode."""
    from repro.launch.mesh import make_batch_mesh
    from repro.serve import BatchedEngine

    shape = (512,) * 100
    eng = BatchedEngine(32, backend="segment", mesh=make_batch_mesh(1),
                        batch_quantum=4)
    assert eng.pod_plan.dispatch_batch(5) == (8, 8)
    with pytest.raises(ValueError, match="mode 0"):
        eng.bucket_plan(shape, 1 << 20)


def test_observed_density_moves_chosen_kappa():
    """The density feedback loop's observable: a stream whose row mass
    concentrates in the top density bin makes the segment cost chooser
    settle on FEWER partitions for that mode (LPT makespan plateaus at
    the heavy rows' mass), while uniform-prior modes keep the larger
    kappa.  Config pinned to (96, 96, 96) cap=768 where the uniform
    chooser picks kappa=8 and the skewed one kappa=4."""
    from repro.core.plan import DENSITY_BINS

    uni = tuple(1.0 / DENSITY_BINS for _ in range(DENSITY_BINS))
    skew = (1.0,) + (0.0,) * (DENSITY_BINS - 1)
    shape, cap = (96, 96, 96), 768
    pu = plan_bucket(shape, cap, rank=4, kappa=8, density=(uni,) * 3)
    ps = plan_bucket(shape, cap, rank=4, kappa=8, density=(skew, uni, uni))
    assert [m.seg_kappa for m in pu.modes] == [8, 8, 8]
    assert [(m.seg_kappa, m.seg_scheme) for m in ps.modes] == [
        (4, "index"), (8, "index"), (8, "index")]
    # Density-less plans never consult the chooser: seg fields reproduce
    # the caller's kappa with no scheme pin (bit-identical legacy paths).
    p0 = plan_bucket(shape, cap, rank=4, kappa=8)
    assert all(m.seg_kappa == 8 and m.seg_scheme is None for m in p0.modes)
