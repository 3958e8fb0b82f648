"""ALS engine benchmark: device-resident fused sweep vs the host loop.

Measures, per Table-3 dataset generator (CI-scaled):

  * wall time per ALS iteration for engine="host" (per-mode device->host
    sync + numpy solve + factor re-upload) vs engine="fused" (one jitted
    sweep, state device-resident), compile excluded via a warm-up run;
  * host syncs per iteration for both engines (the overhead the paper's
    thesis says dominates the small-tensor regime) — asserted, not just
    reported: the fused engine must do <= 1 sync per ``CHECK_EVERY``
    iterations (+1 final materialization);
  * the partition plan each timed config ran under (per-mode block_rows /
    tile / rank_block / slab cap, via ``core.plan``), so a perf regression
    is attributable to a planning change rather than guessed at.

The fused engine's MTTKRP time is in a profiler trace, under the sweep's
``mttkrp/mode<d>`` named scopes.

Output: ``name,us_per_call,derived`` CSV like the other sections.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import cpd_als, make_plan, plan_tensor
from repro.core.als_device import cpd_als_fused

from .common import KAPPA, load_datasets

RANK = 16
ITERS = 6
CHECK_EVERY = 2


def bench_one(name, tensor, *, rank=RANK, iters=ITERS,
              check_every=CHECK_EVERY) -> dict:
    plan = make_plan(tensor, KAPPA)
    # The static plan this tensor's bucket class executes under — printed
    # with every timing row so planning changes are attributable.
    pplan = plan_tensor(tensor, rank, KAPPA)

    # Warm-up both engines (jit compile + plan device upload), then time.
    cpd_als(tensor, rank, plan=plan, n_iters=1, tol=-1.0, engine="host")
    t0 = time.perf_counter()
    host = cpd_als(tensor, rank, plan=plan, n_iters=iters, tol=-1.0,
                   engine="host")
    host_s = time.perf_counter() - t0

    # Warm-up must use the same check window: the scan block length is part
    # of the executable key, so warming with n_iters=1 would leave the
    # window-`check_every` executable to compile inside the timed region.
    cpd_als_fused(tensor, rank, plan=plan, n_iters=check_every, tol=-1.0,
                  check_every=check_every)
    t0 = time.perf_counter()
    fused = cpd_als_fused(tensor, rank, plan=plan, n_iters=iters, tol=-1.0,
                          check_every=check_every)
    fused_s = time.perf_counter() - t0

    # The sync-count probe (acceptance): <= 1 per check_every iters + final.
    budget = -(-iters // check_every) + 1
    assert fused.host_syncs <= budget, (fused.host_syncs, budget)
    assert abs(host.fits[-1] - fused.fits[-1]) < 1e-3, (
        host.fits[-1], fused.fits[-1])

    return {
        "dataset": name,
        "shape": tensor.shape,
        "nnz": tensor.nnz,
        "host_s_per_iter": host_s / iters,
        "fused_s_per_iter": fused_s / iters,
        "speedup": host_s / max(fused_s, 1e-12),
        "host_syncs_per_iter": host.host_syncs / iters,
        "fused_syncs_per_iter": fused.host_syncs / iters,
        "plan": pplan.describe(),
    }


def run(scale: float | None = None) -> list[dict]:
    kw = {} if scale is None else {"scale": scale}
    return [bench_one(name, t) for name, t in load_datasets(**kw).items()]


def main():
    rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"als/{r['dataset']}/host,{r['host_s_per_iter']*1e6:.0f},"
              f"syncs_per_iter={r['host_syncs_per_iter']:.1f}")
        print(f"als/{r['dataset']}/fused,{r['fused_s_per_iter']*1e6:.0f},"
              f"syncs_per_iter={r['fused_syncs_per_iter']:.2f};"
              f"speedup={r['speedup']:.2f}x;plan={r['plan']}")
    gmean = float(np.exp(np.mean([np.log(r["speedup"]) for r in rows])))
    print(f"als/geomean-speedup,0,{gmean:.2f}x")
    return rows


if __name__ == "__main__":
    main()
