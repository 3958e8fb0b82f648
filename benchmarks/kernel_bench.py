"""Pallas MTTKRP kernel micro-bench: VMEM/MXU cost model + interpret-mode
validation timing.

Real TPU wall-time is unavailable in this container (kernels run in
interpret mode), so the kernel is scored by its structural roofline:
per-grid-step VMEM footprint, MXU utilization of the one-hot
gather/scatter matmuls, padding overhead from slab packing, and HBM
traffic — the quantities BlockSpec tiling controls.
"""
from __future__ import annotations

import time

import numpy as np

import jax.numpy as jnp

from repro.core import make_plan, mttkrp, random_sparse
from repro.kernels import ops as kops

from .common import RANK, load_datasets


def kernel_cost_model(packed: kops.PackedModeLayout, factors, *,
                      lane=128, sublane=8) -> dict:
    """Static kernel cost per mode sweep (all grid steps)."""
    T, BR, R = packed.tile, packed.block_rows, factors[0].shape[1]
    W = len(factors)
    G = packed.num_slabs
    # VMEM per step: slabs + output block + resident factors
    vmem = (W * T * 4 + T * 4 + T * 4 + BR * R * 4
            + sum(int(np.prod(f.shape)) * 4 for f in factors))
    # MXU work: scatter matmul (T x BR) @ (T x R) per step (+ gathers when
    # one-hot).  Efficiency = achieved macs / padded-tile macs.
    mxu_macs = G * T * BR * R
    pad_eff = 1.0 - packed.pad_fraction
    lane_eff = min(R, lane) / lane
    hbm = (G * T * (W + 2) * 4) + packed.num_row_blocks * BR * R * 4
    return {
        "grid_steps": G,
        "vmem_bytes_per_step": int(vmem),
        "vmem_ok": vmem < 16 * 2**20,
        "mxu_macs": int(mxu_macs),
        "pad_efficiency": pad_eff,
        "lane_efficiency": lane_eff,
        "hbm_bytes": int(hbm),
    }


def run():
    rows = []
    t = random_sparse((2048, 1024, 512), 100_000, seed=7,
                      distribution="powerlaw")
    plan = make_plan(t, kappa=8)
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.standard_normal((I, RANK)).astype(np.float32))
               for I in t.shape]
    for mode in range(t.nmodes):
        packed = plan.packed(mode)
        in_modes = plan.layouts[mode].input_modes()
        cost = kernel_cost_model(packed, [factors[w] for w in in_modes])
        # beyond-paper: BlockSpec auto-tuning vs the default tiling
        br, tl = kops.auto_tiles(plan.layouts[mode], rank=RANK)
        auto = kops.estimate_pack_cost(
            plan.layouts[mode], br, tl, RANK,
            [t.shape[w] for w in in_modes])
        dflt = kops.estimate_pack_cost(
            plan.layouts[mode], kops.DEFAULT_BLOCK_ROWS, kops.DEFAULT_TILE,
            RANK, [t.shape[w] for w in in_modes])
        # interpret-mode correctness + CPU wall (not TPU-representative)
        t0 = time.perf_counter()
        out_pal = mttkrp(plan, factors, mode, backend="pallas")
        out_pal.block_until_ready()
        wall = time.perf_counter() - t0
        out_ref = mttkrp(plan, factors, mode, backend="segment")
        err = float(jnp.max(jnp.abs(out_pal - out_ref)))
        rows.append({"mode": mode, "wall_s": wall, "max_err": err,
                     "auto_tiles": (br, tl),
                     "auto_cost_gain": dflt["cost"] / auto["cost"],
                     "auto_pad_eff": 1.0 - auto["pad_fraction"], **cost})
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run():
        print(f"kernel/mode{r['mode']},{r['wall_s']*1e6:.0f},"
              f"err={r['max_err']:.1e};grid={r['grid_steps']};"
              f"vmem={r['vmem_bytes_per_step']};vmem_ok={r['vmem_ok']};"
              f"pad_eff={r['pad_efficiency']:.3f};"
              f"auto={r['auto_tiles']};auto_gain={r['auto_cost_gain']:.2f}x;"
              f"auto_pad_eff={r['auto_pad_eff']:.3f}")


if __name__ == "__main__":
    main()
