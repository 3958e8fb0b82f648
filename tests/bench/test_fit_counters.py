"""The fused-ALS counters the benchmark reads per fit, on the tiny segment
cell: ``h2d_mb_per_fit.als`` equals the hand count of what each fit
uploads, ``fit_prepare_ms.als`` is reported, and the per-mode scopes that
the sequential and the vmapped sweeps put on their operations stay inside
the ``mttkrp`` scope that ``bench.trace`` reads."""
import re

import pytest

from bench import harness, trace
from bench_cases import CPU_PEAKS, tiny_cell


def _reading(win) -> harness.Reading:
    return harness.Reading(win.counters, None, None, CPU_PEAKS)


def test_h2d_mb_per_fit_equals_the_hand_count():
    cell = tiny_cell("chicago-als.segment")
    drv = harness.make_load(cell, 2**31 + 21)
    drv.setup()
    win = drv.window(0.5, None)
    assert win.counters["fits"] >= 1
    shape, nnz, rank = drv.tensor.shape, drv.tensor.nnz, drv.rank
    n = len(shape)
    # Per fit: factors, grams and weights (float32), then the fit data:
    # int32 coordinates, float32 values and the float32 squared norm.  The
    # plan's layout arrays went up once, in set-up.
    state = 4 * (rank * sum(shape) + n * rank * rank + rank)
    fit_data = 4 * nnz * n + 4 * nnz + 4
    got = harness.load_reader("h2d_mb_per_fit.als")(_reading(win))
    assert got == pytest.approx((state + fit_data) / 1e6, rel=1e-12)
    prep = harness.load_reader("fit_prepare_ms.als")(_reading(win))
    assert prep > 0.0


def test_readers_give_nothing_without_fits():
    win = type("W", (), {"counters": {"fits": 0}})()
    for name in ("h2d_mb_per_fit.als", "fit_prepare_ms.als"):
        assert harness.load_reader(name)(_reading(win)) is None


def _op_names(lowered) -> set[str]:
    return set(re.findall(r'loc\("([^"]*mttkrp[^"]*)"',
                          lowered.as_text(debug_info=True)))


def test_mode_scopes_sit_inside_the_mttkrp_scope():
    """Sequential block paths (``.../mttkrp/mode0/...``) and vmapped ones
    (``vmap(mttkrp)/vmap(mode0)`` or the like) all match ``in_scope``."""
    import jax.numpy as jnp

    from repro.core import als_device, make_plan, random_sparse
    from repro.serve.batched_engine import BatchedEngine, _build_batched_block

    t = random_sparse((10, 8, 6), 200, seed=13)
    plan = make_plan(t, 1)
    mode_data, meta = als_device._collect_mode_data(plan, "segment", 4)
    fit_data = (jnp.asarray(t.indices), jnp.asarray(t.values, jnp.float32),
                jnp.asarray(1.0, jnp.float32))
    seq = als_device._build_sweep_block(
        "segment", 3, 4, t.shape, None, True, False, "inv", 1, "cp").lower(
        als_device.init_state(t.shape, 4, 0), mode_data, fit_data)

    eng = BatchedEngine(4, check_every=1)
    prep = eng.prepare_batch([t, t], n_iters=1, tol=-1.0, nnz_cap=200)
    batched = _build_batched_block(
        "segment", 3, 4, prep.shape, prep.cap, prep.batch, eng.interpret,
        False, eng.solver, 1, None, "cp").lower(
        prep.carry, prep.mode_data_all, prep.fit_data, prep.tol_dev,
        prep.max_iters_dev)
    for lowered in (seq, batched):
        names = [n for n in _op_names(lowered)
                 if re.search(r"mode\d", n)]
        assert names
        for name in names:
            assert trace.in_scope(name + ":", "mttkrp"), name
