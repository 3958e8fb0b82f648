"""Distributed ALS smoke benchmark: the shard_map fused sweep on a
virtual 8-device CPU mesh.

jax pins its device count at first init, so the measured run happens in a
fresh subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count``
set — the same trick ``tests/distributed`` uses — and this module just
parses its CSV back out.  Measured per Table-3-style tensor:

  * wall time per ALS iteration for single-device fused vs distributed
    (8 virtual devices; on CPU the shards serialize, so this is a
    correctness/overhead smoke, not a scaling claim);
  * host syncs per iteration for the distributed engine — asserted <= 1
    per ``check_every`` window (+1 final), i.e. zero per-iteration syncs
    inside a window;
  * the fp32 agreement of the final fit with the single-device engine;
  * a masked/weighted completion row (``method="masked"`` with
    fractional observation confidences): per-shard residual scatter,
    psum of partial valued MTTKRPs, weighted sharded fit — the
    distributed path of the weighted-observations front door;
  * per-tensor collective-payload accounting: bytes moved per sweep by
    the full-array psum vs the scheme-1 row-sharded all-gather
    (``collective="gather"``), with an fp32 agreement check between the
    two collectives.

Output: ``name,us_per_call,derived`` CSV like the other sections, plus
``ROW {json}`` lines the runner stores as BENCH_dist.json rows.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

from .common import host_device_env

DEVICES = 8

_CHILD = """
    import json
    import time
    import numpy as np
    from repro.core import cpd_als, random_sparse
    from repro.core.distributed import (
        collective_payload_bytes, cpd_als_distributed,
        make_distributed_plan, resolve_collectives)

    def row(r):
        print("ROW " + json.dumps(r))

    ITERS, CHECK = 6, 3
    for name, shape, nnz in (("uber-like", (60, 24, 160), 2000),
                             ("tiny-mode", (48, 32, 6), 1500)):
        t = random_sparse(shape, nnz, seed=7, distribution="powerlaw")
        # warm-up (compile + plan build), then time
        single = cpd_als(t, rank=8, n_iters=1, tol=-1.0, check_every=1)
        t0 = time.perf_counter()
        single = cpd_als(t, rank=8, n_iters=ITERS, tol=-1.0,
                         check_every=CHECK)
        single_s = time.perf_counter() - t0

        plan = make_distributed_plan(t)
        cpd_als_distributed(t, rank=8, plan=plan, n_iters=CHECK, tol=-1.0,
                            check_every=CHECK)
        t0 = time.perf_counter()
        dist = cpd_als_distributed(t, rank=8, plan=plan, n_iters=ITERS,
                                   tol=-1.0, check_every=CHECK)
        dist_s = time.perf_counter() - t0

        assert dist.host_syncs <= ITERS // CHECK + 1, dist.host_syncs
        assert abs(dist.fits[-1] - single.fits[-1]) < 1e-3, (
            dist.fits[-1], single.fits[-1])
        schemes = "/".join(m.scheme.name[0] + m.scheme.name[-1]
                           for m in plan.modes)
        print(f"dist/{name}/single,{single_s / ITERS * 1e6:.0f},"
              f"fit={single.fits[-1]:.4f}")
        print(f"dist/{name}/shard_map-8dev,{dist_s / ITERS * 1e6:.0f},"
              f"fit={dist.fits[-1]:.4f};"
              f"syncs_per_iter={dist.host_syncs / ITERS:.2f};"
              f"schemes={schemes}")
        row({"name": f"dist/{name}", "section": "als",
             "single_us_per_iter": single_s / ITERS * 1e6,
             "dist_us_per_iter": dist_s / ITERS * 1e6,
             "fit": float(dist.fits[-1]),
             "syncs_per_iter": dist.host_syncs / ITERS,
             "schemes": schemes})

        # Collective payload: scheme-1 modes swap the full (I_d, R) psum
        # for an all-gather of each device's owned row slice (+ int32
        # destination map); the gather run must agree with psum to fp32.
        cols = resolve_collectives(plan, "gather")
        psum_b = collective_payload_bytes(plan, 8, None)
        gath_b = collective_payload_bytes(plan, 8, cols)
        if cols is not None:
            g = cpd_als_distributed(t, rank=8, plan=plan, n_iters=ITERS,
                                    tol=-1.0, check_every=CHECK,
                                    collective="gather")
            assert abs(g.fits[-1] - dist.fits[-1]) < 1e-3, (
                g.fits[-1], dist.fits[-1])
        row({"name": f"dist/{name}/collective", "section": "collective",
             "collectives": list(cols) if cols is not None else None,
             "psum_payload_bytes": psum_b,
             "gather_payload_bytes": gath_b,
             "payload_ratio": psum_b / gath_b if gath_b else None})
        print(f"dist/{name}/collective,0,psum_B={psum_b};"
              f"gather_B={gath_b};ratio={psum_b / max(gath_b, 1):.2f}")

    # Masked/weighted completion under shard_map: per-shard residual
    # scatter + psum of partial valued MTTKRPs, weighted sharded fit.
    t = random_sparse((48, 32, 6), 1500, seed=7, distribution="powerlaw")
    w = np.random.default_rng(1).uniform(0.25, 1.0, t.nnz).astype(np.float32)
    single = cpd_als(t, rank=8, n_iters=ITERS, tol=-1.0, check_every=CHECK,
                     method="masked", weights=w)
    mplan = make_distributed_plan(t, method="masked", weights=w)
    cpd_als_distributed(t, rank=8, plan=mplan, n_iters=CHECK, tol=-1.0,
                        check_every=CHECK, method="masked")
    t0 = time.perf_counter()
    dist = cpd_als_distributed(t, rank=8, plan=mplan, n_iters=ITERS,
                               tol=-1.0, check_every=CHECK, method="masked")
    dist_s = time.perf_counter() - t0
    assert dist.host_syncs <= ITERS // CHECK + 1, dist.host_syncs
    assert abs(dist.fits[-1] - single.fits[-1]) < 1e-3, (
        dist.fits[-1], single.fits[-1])
    print(f"dist/masked-weighted/shard_map-8dev,{dist_s / ITERS * 1e6:.0f},"
          f"fit={dist.fits[-1]:.4f};single_fit={single.fits[-1]:.4f};"
          f"syncs_per_iter={dist.host_syncs / ITERS:.2f}")
    row({"name": "dist/masked-weighted", "section": "als",
         "dist_us_per_iter": dist_s / ITERS * 1e6,
         "fit": float(dist.fits[-1]),
         "syncs_per_iter": dist.host_syncs / ITERS})
"""


def run(devices: int = DEVICES) -> str:
    env = host_device_env(devices)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CHILD)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"distributed smoke failed:\n{out.stdout}\n"
                           f"{out.stderr}")
    return out.stdout


def main() -> list[dict]:
    print("name,us_per_call,derived")
    rows = []
    for line in run().splitlines():
        if line.startswith("ROW "):
            rows.append(json.loads(line[4:]))
        else:
            print(line)
    return rows


if __name__ == "__main__":
    main()
