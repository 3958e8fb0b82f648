"""Chip smoke test: the main path, end to end, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: distributed ALS, pod serving

One chip runs three phases on FROSTT chicago at its full Table III size
(6186 x 24 x 77 x 32, 5,330,673 nonzeros, generated from a seed by
``core.coo.frostt_like``), rank 32:

  (a) MTTKRP on every mode with the segment and the compiled Pallas
      backends, each checked against a float64 numpy COO reference;
  (b) a few iterations of the fused ``cpd_als`` with both backends, checked
      against the host loop ``cpd_als(engine="host")`` from the same seed;
  (c) ``DecompositionService`` with both backends answering 16 requests
      drawn from two ``benchmarks/serve_bench.STREAM_SHAPES`` families, each
      result checked against a sequential ``cpd_als`` of the same tensor.

``--chips 4`` runs only the multi-chip paths: the 16-request stream on
``make_batch_mesh(4)`` against the single-device batched engine, and
``cpd_als_distributed`` on chicago over a 4-device mesh against
single-device ``cpd_als``; it also checks that data landed on every device.

Each phase prints its wall seconds for a first call (which compiles) and a
second, steady call, the device's peak bytes and its largest error.  Any
mismatch or exception exits non-zero, and so does a run that finds no TPU:
the phases never fall back to the CPU.  The last line of a passing run is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

DATASET = "chicago"
RANK = 32
ALS_ITERS = 4
BACKENDS = ("segment", "pallas")
SERVE_FAMILIES = ("chicago-like", "uber-like")
SERVE_PER_FAMILY = 8
SERVE_RANK = 8
SERVE_ITERS = 8
SERVE_CHECK = 4
# max |out - ref| / max |ref| of one MTTKRP against the float64 reference.
# On a v5e: 2.4e-5 for segment (f32 accumulation over fibers of up to
# ~2*10^5 nonzeros) and 1.1e-6 for the kernel.
MTTKRP_TOL = 1e-4
# Largest |fit difference| at any iteration between two ALS front doors.
# A fit is 1 - ||X - model|| / ||X||, so the gap is the difference of two
# residual norms as a share of ||X||: relative to the data already, and
# still meaningful where the fit itself is near 0, as on chicago's
# random values (fit 0.0038).  On a v5e the gaps are at most 1.8e-7 with
# the fused sweeps' f32 matmuls, and 1.3e-6 to 2.0e-6 against the host
# loop with the TPU's default one-pass bf16 matmuls.
FIT_TOL = 5e-7


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(device) -> int | None:
    """``peak_bytes_in_use`` of ``device``; None where not reported."""
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def load_tensor(scale: float = 1.0):
    from repro.core import frostt_like

    return frostt_like(DATASET, scale=scale, seed=0)


def full_tensor():
    """``DATASET`` at its Table III shape and nnz, checked, and reported."""
    from repro.core.coo import FROSTT_SHAPES

    tensor, t_gen = _timed(load_tensor)
    shape, nnz = FROSTT_SHAPES[DATASET]
    _check(tensor.shape == shape and tensor.nnz == nnz,
           f"{DATASET} came out {tensor.shape} with {tensor.nnz} nonzeros, "
           f"not its Table III {shape} with {nnz}")
    _emit("tensor", {"dataset": DATASET, "shape": list(tensor.shape),
                     "nnz": tensor.nnz, "rank": RANK, "gen_s": t_gen})
    return tensor


def mttkrp_reference(tensor, factors, mode: int, chunk: int = 1 << 20):
    """Float64 MTTKRP over the COO list, on the host, in nnz chunks."""
    import numpy as np

    rank = factors[0].shape[1]
    out = np.zeros((tensor.shape[mode], rank))
    for s in range(0, tensor.nnz, chunk):
        idx = tensor.indices[s:s + chunk]
        acc = np.repeat(tensor.values[s:s + chunk, None].astype(np.float64),
                        rank, axis=1)
        for w, f in enumerate(factors):
            if w != mode:
                acc *= f[idx[:, w]].astype(np.float64)
        for r in range(rank):
            out[:, r] += np.bincount(idx[:, mode], weights=acc[:, r],
                                     minlength=tensor.shape[mode])
    return out


def _fit_gap(a, b) -> float:
    _check(len(a.fits) == len(b.fits),
           f"fit histories differ in length: {len(a.fits)} vs {len(b.fits)}")
    return max(abs(x - y) for x, y in zip(a.fits, b.fits))


def phase_mttkrp(tensor, rank: int = RANK, seed: int = 0) -> dict:
    """(a) MTTKRP on every mode, both backends, against the f64 reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_plan, mttkrp

    rng = np.random.default_rng(seed)
    factors = [rng.standard_normal((n, rank)).astype(np.float32)
               for n in tensor.shape]
    dev = [jnp.asarray(f) for f in factors]
    plan = make_plan(tensor, kappa=1)
    refs = [mttkrp_reference(tensor, factors, d)
            for d in range(tensor.nmodes)]
    report = {}
    for backend in BACKENDS:
        first = steady = err = 0.0
        for d in range(tensor.nmodes):
            def call():
                return mttkrp(plan, dev, d, backend=backend)
            _, t1 = _timed(call)
            out, t2 = _timed(call)
            first, steady = first + t1, steady + t2
            scale = max(float(np.abs(refs[d]).max()), 1e-30)
            e = float(np.abs(np.asarray(out, np.float64) - refs[d]).max())
            err = max(err, e / scale)
        _check(err <= MTTKRP_TOL,
               f"{backend} MTTKRP off the f64 reference by {err:.3e}")
        report[backend] = {"first_s": first, "steady_s": steady,
                           "max_rel_err": err}
    return report


def phase_als(tensor, rank: int = RANK, iters: int = ALS_ITERS,
              seed: int = 0) -> dict:
    """(b) fused ``cpd_als``, both backends, against the host loop."""
    from repro.core import cpd_als, make_plan

    plan = make_plan(tensor, kappa=1)
    kw = dict(plan=plan, n_iters=iters, tol=-1.0, seed=seed)
    host, t_host = _timed(lambda: cpd_als(tensor, rank, engine="host", **kw))
    report = {"host": {"steady_s": t_host, "fit": host.fits[-1]}}
    for backend in BACKENDS:
        def call():
            return cpd_als(tensor, rank, backend=backend, check_every=iters,
                           **kw)
        _, t1 = _timed(call)
        res, t2 = _timed(call)
        gap = _fit_gap(res, host)
        _check(gap <= FIT_TOL,
               f"fused {backend} fits off the host loop by {gap:.3e}: "
               f"{res.fits} vs {host.fits}")
        report[backend] = {"first_s": t1, "steady_s": t2,
                           "fit": res.fits[-1], "max_fit_gap": gap,
                           "host_syncs": res.host_syncs}
    return report


def serve_stream(per_family: int = SERVE_PER_FAMILY):
    """The request stream: ``per_family`` tensors of each family."""
    from benchmarks.serve_bench import STREAM_SHAPES, make_stream

    out = []
    for name in SERVE_FAMILIES:
        shape, nnz = STREAM_SHAPES[name]
        out += make_stream(shape, nnz, per_family)
    return out


def _serve(svc, tensors) -> list:
    futs = [svc.submit(t, n_iters=SERVE_ITERS, tol=-1.0, seed=i)
            for i, t in enumerate(tensors)]
    svc.drain()
    # The scheduler forwards engine errors into the futures: read them all.
    return [f.result() for f in futs]


def phase_service(tensors, backend: str, *, mesh=None) -> dict:
    """(c) ``DecompositionService`` on one backend.  Single device: each
    result against a sequential ``cpd_als``.  With ``mesh`` (the pod
    path): against the single-device batched engine."""
    from repro.core import cpd_als
    from repro.serve import BatchedEngine, DecompositionService

    svc = DecompositionService(SERVE_RANK, backend=backend,
                               check_every=SERVE_CHECK,
                               max_batch=SERVE_PER_FAMILY, mesh=mesh)
    _, t1 = _timed(lambda: _serve(svc, tensors))
    got, t2 = _timed(lambda: _serve(svc, tensors))
    want_engine = "batched" if mesh is None else "pod"
    _check(all(r.engine == want_engine for r in got),
           f"service answered with engines {sorted({r.engine for r in got})}")
    if mesh is None:
        refs = [cpd_als(t, SERVE_RANK, n_iters=SERVE_ITERS, tol=-1.0, seed=i,
                        backend=backend, check_every=SERVE_CHECK)
                for i, t in enumerate(tensors)]
    else:
        eng = BatchedEngine(SERVE_RANK, backend=backend,
                            check_every=SERVE_CHECK)
        per_family = len(tensors) // len(SERVE_FAMILIES)
        refs = []
        for lo in range(0, len(tensors), per_family):
            batch = tensors[lo:lo + per_family]
            refs += eng.decompose_batch(
                batch, n_iters=SERVE_ITERS, tol=-1.0,
                seeds=list(range(lo, lo + len(batch))),
                nnz_cap=svc.scheduler.policy.nnz_cap(
                    max(t.nnz for t in batch)))
    gap = max(_fit_gap(a, b) for a, b in zip(got, refs))
    _check(gap <= FIT_TOL,
           f"service ({backend}) fits off the reference by {gap:.3e}")
    return {"requests": len(tensors), "first_s": t1, "steady_s": t2,
            "max_fit_gap": gap}


def phase_distributed(tensor, devices, rank: int = RANK,
                      iters: int = ALS_ITERS, seed: int = 0) -> dict:
    """``cpd_als_distributed`` over ``devices`` against single-device
    ``cpd_als``, plus a witness that every device received its shard."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import cpd_als
    from repro.core.distributed import (AXIS, cpd_als_distributed,
                                        make_distributed_plan)

    mesh = Mesh(np.array(devices), (AXIS,))
    plan = make_distributed_plan(tensor, mesh)
    kw = dict(n_iters=iters, tol=-1.0, seed=seed, check_every=iters)
    single, t_single = _timed(lambda: cpd_als(tensor, rank, **kw))
    _, t1 = _timed(lambda: cpd_als_distributed(tensor, rank, plan=plan, **kw))
    dist, t2 = _timed(lambda: cpd_als_distributed(tensor, rank, plan=plan,
                                                  **kw))
    gap = _fit_gap(dist, single)
    _check(gap <= FIT_TOL,
           f"distributed fits off single-device cpd_als by {gap:.3e}")
    # Bytes of one device's slice of the sharded mode data.
    shard = sum(a.nbytes // len(devices) for m in plan.modes
                for a in (m.idx, m.rows, m.vals))
    peaks = [peak_bytes(d) for d in devices]
    if all(p is not None for p in peaks):
        _check(all(p >= shard for p in peaks),
               f"a device never held its {shard}-byte shard: peaks {peaks}")
    return {"devices": len(devices), "first_s": t1, "steady_s": t2,
            "single_device_s": t_single, "max_fit_gap": gap,
            "shard_bytes": shard, "peak_bytes": peaks}


def _emit(name: str, report: dict) -> None:
    print(f"{name}: {json.dumps(report, sort_keys=True)}", flush=True)


def run_one_chip(device) -> None:
    from repro.kernels.mttkrp_pallas import resolve_interpret

    _check(resolve_interpret(None) is False,
           "the Pallas kernel would be interpreted on this device")
    tensor = full_tensor()
    for name, fn in (("a_mttkrp", lambda: phase_mttkrp(tensor)),
                     ("b_als", lambda: phase_als(tensor))):
        report = fn()
        report["peak_bytes"] = peak_bytes(device)
        _emit(name, report)
    tensors = serve_stream()
    for backend in BACKENDS:
        report = phase_service(tensors, backend)
        report["peak_bytes"] = peak_bytes(device)
        _emit(f"c_service_{backend}", report)


def run_four_chips(devices) -> None:
    from repro.launch.mesh import make_batch_mesh

    # The pod stream first: in a fresh process any bytes on devices 1-3
    # come from its shard_map.
    tensors = serve_stream()
    mesh = make_batch_mesh(len(devices))
    for backend in BACKENDS:
        report = phase_service(tensors, backend, mesh=mesh)
        report["peak_bytes"] = [peak_bytes(d) for d in devices]
        _check(all(p is None or p > 0 for p in report["peak_bytes"]),
               f"a pod device held no data: {report['peak_bytes']}")
        _emit(f"pod_service_{backend}", report)
    _emit("distributed", phase_distributed(full_tensor(), devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c); 4: the multi-chip paths only")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax sees {dev.platform}); "
              f"the smoke runs only on a TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax sees {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    _emit("device", {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices)})
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(dev)
    else:
        run_four_chips(devices)
    _emit("total", {"wall_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
