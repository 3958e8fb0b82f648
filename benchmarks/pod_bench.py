"""Pod serving benchmark: mesh-sharded batch axis + on-device convergence
+ double-buffered dispatch, on a virtual 8-device CPU mesh.

jax pins its device count at first init, so the measured run happens in a
fresh subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count``
set (the same trick ``dist_bench`` and ``tests/distributed`` use); the
child prints one ``ROW {json}`` line per witness and this module parses
them back into the runner's structured rows.  The witnesses:

  * ``pod/one-dispatch`` — a multi-window decomposition is ONE device
    dispatch: exactly one ``pod.dispatch`` span in the trace, every
    result reports ``host_syncs == 1``, and the on-device while_loop ran
    all its windows (``pod.window`` event);
  * ``pod/load-balance`` — per-device nnz load of the dispatched lanes
    (shard_map splits the batch into contiguous per-device blocks) and
    the max/mean imbalance factor;
  * ``pod/lane-placement`` — load-aware lane placement on a jittered-nnz
    2-lanes-per-device batch: the placed per-device imbalance must be no
    worse than the arrival-order contiguous split (both read from the
    same ``pod.dispatch`` span);
  * ``pod/agreement`` — max fp32 deviation of the pod factors/fits from
    the single-device batched engine on the same requests;
  * ``pod/overlap`` — a double-buffered service stream through the pod
    engine: overlap fraction (host assembly hidden behind device
    compute) must be > 0, plus device occupancy and per-device dispatch
    counts;
  * ``pod/ledger`` — pod-block executables and their retrace ceiling
    (one trace per registered block; more is a jit cache
    re-specializing).

On CPU the 8 virtual shards serialize, so wall times here are
correctness/overhead smokes, not scaling claims.
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
    import numpy as np
    from repro.core import random_sparse
    from repro.launch.mesh import make_batch_mesh
    from repro.obs import trace as obs_trace
    from repro.obs.ledger import LEDGER
    from repro.serve import BatchedEngine
    from repro.serve.scheduler import DecompositionService

    SMOKE = {smoke}
    RANK = 3 if SMOKE else 8
    B, NNZ, ITERS, CHECK = (8, 480, 10, 2) if SMOKE else (16, 1500, 24, 3)
    SHAPE = (18, 13, 9) if SMOKE else (60, 24, 40)

    def row(r):
        print("ROW " + json.dumps(r))

    ts = [random_sparse(SHAPE, NNZ - 7 * i, seed=i,
                        distribution="powerlaw") for i in range(B)]
    cap = NNZ
    kw = dict(n_iters=ITERS, tol=-1.0, seeds=list(range(B)), nnz_cap=cap)

    plain = BatchedEngine(rank=RANK, kappa=2, backend="segment",
                          check_every=CHECK)
    ref = plain.decompose_batch(ts, **kw)

    pod = BatchedEngine(rank=RANK, kappa=2, backend="segment",
                        check_every=CHECK, mesh=make_batch_mesh({devices}))
    pod.decompose_batch(ts[:1], n_iters=CHECK, tol=-1.0, seeds=[0],
                        nnz_cap=cap)                       # warm 1-lane pod
    with obs_trace.capture() as tr:
        res = pod.decompose_batch(ts, **kw)
    events = tr.records()
    dispatches = [e for e in events if e["name"] == "pod.dispatch"]
    windows = [e for e in events if e["name"] == "pod.window"]
    assert len(dispatches) == 1 and len(windows) == 1, (
        [e["name"] for e in events])
    row({{"name": "pod/one-dispatch", "section": "dispatch",
         "pod_dispatch_spans": len(dispatches),
         "host_syncs": max(r.host_syncs for r in res),
         "windows": windows[0]["args"]["windows"],
         "max_windows": dispatches[0]["args"]["max_windows"],
         "sweeps_per_window": CHECK, "devices": {devices}, "B": B}})

    dev_nnz = dispatches[0]["args"]["device_nnz"]
    mean = sum(dev_nnz) / len(dev_nnz)
    row({{"name": "pod/load-balance", "section": "balance",
         "device_nnz": dev_nnz,
         "imbalance": max(dev_nnz) / mean if mean else 1.0}})

    fit_err = max(float(np.abs(np.asarray(a.fits)
                               - np.asarray(b.fits)).max())
                  for a, b in zip(res, ref))
    fac_err = max(float(np.abs(np.asarray(Fa) - np.asarray(Fb)).max())
                  for a, b in zip(res, ref)
                  for Fa, Fb in zip(a.factors, b.factors))
    row({{"name": "pod/agreement", "section": "agreement",
         "max_fit_err": fit_err, "max_factor_err": fac_err,
         "tolerance": 1e-3}})
    assert fit_err < 1e-3 and fac_err < 1e-2, (fit_err, fac_err)

    # Load-aware lane placement: 2 lanes/device with shuffled jittered
    # nnz — the placed (heaviest-first greedy) per-device load must be
    # no worse than the arrival-order contiguous split, both recorded
    # on the same pod.dispatch span.
    rng = np.random.default_rng(0)
    sizes = rng.permutation([max(NNZ - 23 * i, 40)
                             for i in range(2 * {devices})]).tolist()
    ts2 = [random_sparse(SHAPE, int(s), seed=200 + i,
                         distribution="powerlaw")
           for i, s in enumerate(sizes)]
    with obs_trace.capture() as tr2:
        pod.decompose_batch(ts2, n_iters=CHECK, tol=-1.0,
                            seeds=list(range(len(ts2))), nnz_cap=cap)
    d2 = [e for e in tr2.records()
          if e["name"] == "pod.dispatch"][0]["args"]
    assert d2["lane_placement"] == "balanced", d2
    assert d2["imbalance"] <= d2["imbalance_contiguous"] + 1e-9, d2
    row({{"name": "pod/lane-placement", "section": "balance",
         "B": len(ts2), "devices": {devices},
         "imbalance": d2["imbalance"],
         "imbalance_contiguous": d2["imbalance_contiguous"],
         "imbalance_delta": d2["imbalance_contiguous"] - d2["imbalance"],
         "device_nnz": d2["device_nnz"],
         "device_nnz_contiguous": d2["device_nnz_contiguous"]}})

    # Double-buffered stream through the pod engine: 3 flushes, each
    # flush's host assembly overlapping the previous flush's dispatch.
    svc = DecompositionService(rank=RANK, max_batch={devices},
                               mesh=make_batch_mesh({devices}),
                               double_buffer=True)
    futs = [svc.submit(random_sparse(SHAPE, NNZ, seed=100 + i,
                                     distribution="powerlaw"),
                       n_iters=ITERS, tol=-1.0, seed=i)
            for i in range(3 * {devices})]
    svc.drain()
    for f in futs:
        assert f.result().engine == "pod"
    d = svc.snapshot()["dispatch"]
    row({{"name": "pod/overlap", "section": "overlap",
         "dispatches": d["count"],
         "overlap_fraction": d["overlap_fraction"],
         "assembly_s": d["assembly_s"], "execute_s": d["execute_s"],
         "device_occupancy": d["device_occupancy"],
         "device_dispatches": d["device_dispatches"]}})

    s = LEDGER.stats("pod_block")
    row({{"name": "pod/ledger", "section": "ledger",
         "blocks": s["blocks"], "traces": s["traces"],
         "expected_max_traces": s["blocks"]}})
"""


def run(devices: int = DEVICES, smoke: bool = False) -> str:
    env = host_device_env(devices)
    code = textwrap.dedent(_CHILD).format(devices=devices, smoke=smoke)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"pod smoke failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


def main(argv: list[str] | None = None) -> list[dict]:
    argv = list(sys.argv[1:] if argv is None else argv)
    smoke = "--smoke" in argv
    rows = []
    print("name,us_per_call,derived")
    for line in run(smoke=smoke).splitlines():
        if not line.startswith("ROW "):
            continue
        r = json.loads(line[4:])
        rows.append(r)
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("name", "section"))
        print(f"{r['name']},0,{derived}")
    return rows


if __name__ == "__main__":
    main()
