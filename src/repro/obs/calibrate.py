"""Cost-model-vs-measured validation harness.

ROADMAP open item 2 flagged that the planning cost models
(``kernels.ops.auto_tiles`` / ``auto_rank_block``,
``benchmarks/device_model.py``) had never been validated against
observed timings.  This module is the measurement side of that loop:

  * ``measure_mode_seconds``   — warm per-mode MTTKRP wall time for a
    backend, measured through *tracer spans* (the numbers reported are
    read back out of the span records, so the harness exercises the
    tracing subsystem end to end rather than keeping a private
    stopwatch).
  * ``measure_shard_imbalance`` — per-mode load-imbalance factor
    (max/mean shard compute time) under a κ-way partition, the
    8-virtual-device mesh by default.  Shards are timed SERIALLY and
    UNPADDED on host (pure numpy segmented MTTKRP): the distributed
    path's rectangular padded shards would equalize the arithmetic and
    destroy exactly the signal being measured.  The measured factor is
    joined against the nnz-count imbalance the partitioner itself
    predicts (``core.load_balance.Partitioning.imbalance``).
  * ``measure_compile_steady`` — runs the fused ALS driver under the
    active tracer and splits the first (cold: trace+compile+execute)
    ``als.window`` span from the median warm window.
  * ``calibrate_tensor``       — one dataset end to end: joins an
    injected ``predict_fn`` (``benchmarks/obs_bench.py`` supplies the
    ``device_model`` predictor; src must not import benchmarks) against
    the measured per-mode seconds, producing the BENCH_obs row schema
    with ``predicted_over_observed`` per backend and the imbalance
    witness per mode.

The predicted/observed RATIO is the honest unit here: the device model
prices an RTX-3090-class GPU while CI measures on CPU (and the pallas
backend under interpret mode), so ratios are expected to sit far from
1.0 — what the harness pins is that they exist, are finite, and stay
STABLE per backend, which is what makes relative cost comparisons
(tiling choices, scheme selection) trustworthy.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np

import jax

from ..core import als_device
from ..core.coo import SparseTensor
from ..core.layout import build_mode_layout
from ..core.load_balance import partition_mode
from ..core.mttkrp import collect_mode_data, make_plan, mttkrp_mode
from . import trace as obs_trace
from .clock import now as _now
from .ledger import LEDGER

DEFAULT_MESH_KAPPA = 8   # the CI "8-virtual-device mesh" width


# ---------------------------------------------------------------------------
# Per-mode measured MTTKRP (device path, through the tracer)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mode_mttkrp_fn(backend: str, nmodes: int, rank: int,
                    shapes: tuple[int, ...], pallas_meta: tuple | None,
                    d: int):
    """Jitted single-mode MTTKRP dispatcher on the shared substrate (the
    same kernels every engine runs).  Registered in the retrace ledger
    like any other executable cache."""
    meta = None if pallas_meta is None else pallas_meta[d]

    def run(mode_data, factors):
        return mttkrp_mode(backend, d, mode_data, factors, shapes[d],
                           meta=meta, interpret=True)

    return LEDGER.register(
        "calibrate_mode", (backend, nmodes, rank, shapes, "mode", d),
        jax.jit(run))


def _random_factors(shapes, rank: int, seed: int):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp
    return [jnp.asarray(rng.standard_normal((I, rank)).astype(np.float32))
            for I in shapes]


def _span_seconds(tr, start_idx: int, name: str) -> list[float]:
    """Durations (s) of spans named ``name`` recorded since start_idx."""
    return [r["dur_us"] / 1e6 for r in tr.records()[start_idx:]
            if r.get("kind") == "span" and r["name"] == name]


def measure_mode_seconds(tensor: SparseTensor, rank: int, backend: str,
                         *, reps: int = 3, seed: int = 0,
                         dataset: str = "?") -> list[float]:
    """Warm wall seconds of ONE MTTKRP per mode (best of ``reps``),
    measured via ``calibrate.mode_mttkrp`` spans on the active tracer
    (a private fallback timer is used only when tracing is off)."""
    tr = obs_trace.installed()
    N = tensor.nmodes
    shapes = tuple(int(s) for s in tensor.shape)
    plan = make_plan(tensor, 1)
    mode_data_all, pallas_meta = collect_mode_data(plan, backend, rank)
    factors = _random_factors(shapes, rank, seed)
    out = []
    for d in range(N):
        fn = _mode_mttkrp_fn(backend, N, rank, shapes, pallas_meta, d)
        jax.block_until_ready(fn(mode_data_all[d], factors))   # compile/warm
        best = None
        for r in range(reps):
            if tr is None:
                t0 = _now()
                jax.block_until_ready(fn(mode_data_all[d], factors))
                dt = _now() - t0
            else:
                i0 = len(tr.records())
                with tr.span("calibrate.mode_mttkrp", cat="calibrate",
                             dataset=dataset, backend=backend, mode=d,
                             rep=r, nnz=tensor.nnz):
                    jax.block_until_ready(fn(mode_data_all[d], factors))
                dt = _span_seconds(tr, i0, "calibrate.mode_mttkrp")[-1]
            best = dt if best is None else min(best, dt)
        out.append(float(best))
    return out


# ---------------------------------------------------------------------------
# Measured per-shard load imbalance (serial, unpadded, pure numpy)
# ---------------------------------------------------------------------------


def _numpy_shard_mttkrp(idx, rows, vals, in_factors, rank: int):
    """Segmented MTTKRP of one shard's (sorted-row) slice in numpy.
    Work scales with the shard's real nnz — no padding, no jit — which
    is what makes per-shard wall time a faithful load proxy."""
    if len(vals) == 0:
        return np.zeros((0, rank), np.float32)
    acc = vals[:, None] * in_factors[0][idx[:, 0]]
    for j in range(1, idx.shape[1]):
        acc = acc * in_factors[j][idx[:, j]]
    starts = np.concatenate(
        [[0], np.flatnonzero(np.diff(rows)) + 1]).astype(np.int64)
    return np.add.reduceat(acc, starts, axis=0)


def measure_shard_imbalance(tensor: SparseTensor, rank: int, *,
                            kappa: int = DEFAULT_MESH_KAPPA,
                            reps: int = 20, seed: int = 0,
                            dataset: str = "?") -> list[dict]:
    """Per-mode measured load-imbalance factor under a κ-way partition.

    For each mode: build the real layout (scheme chosen by the adaptive
    rule, greedy assignment — exactly what the distributed path runs),
    time each shard's segmented MTTKRP serially over ``reps``
    repetitions, and report ``max/mean`` shard time next to the
    partitioner's own nnz-count prediction.  One span per mode carries
    both, so the imbalance table is reconstructible from a trace alone.
    """
    rng = np.random.default_rng(seed)
    shapes = tuple(int(s) for s in tensor.shape)
    in_factors_all = [rng.standard_normal((I, rank)).astype(np.float32)
                      for I in shapes]
    rows_out = []
    for d in range(tensor.nmodes):
        lay = build_mode_layout(tensor, d, kappa)
        part = partition_mode(tensor, d, kappa, scheme=lay.scheme)
        in_modes = lay.input_modes()
        facs = [in_factors_all[w] for w in in_modes]
        off = lay.part_offsets
        with obs_trace.span("calibrate.imbalance", cat="calibrate",
                            dataset=dataset, mode=d, kappa=kappa,
                            scheme=lay.scheme.name) as sp:
            times = []
            for p in range(kappa):
                s, e = int(off[p]), int(off[p + 1])
                idx = lay.indices[s:e][:, in_modes]
                rws = lay.rows[s:e]
                vls = lay.values[s:e].astype(np.float32)
                _numpy_shard_mttkrp(idx, rws, vls, facs, rank)  # warm caches
                t0 = _now()
                for _ in range(reps):
                    _numpy_shard_mttkrp(idx, rws, vls, facs, rank)
                times.append((_now() - t0) / reps)
            times_arr = np.asarray(times)
            mean = float(times_arr.mean())
            measured = float(times_arr.max() / mean) if mean > 0 else 1.0
            predicted = float(part.imbalance())
            sp.set(measured_imbalance=round(measured, 4),
                   nnz_imbalance=round(predicted, 4))
        rows_out.append({
            "mode": d,
            "scheme": lay.scheme.name,
            "shard_nnz": [int(x) for x in np.diff(off)],
            "measured_imbalance": measured,
            "nnz_imbalance": predicted,
            "mean_shard_s": mean,
            "max_shard_s": float(times_arr.max()),
        })
    return rows_out


# ---------------------------------------------------------------------------
# Compile-time vs steady-state split (from als.window spans)
# ---------------------------------------------------------------------------


def measure_compile_steady(tensor: SparseTensor, rank: int, backend: str,
                           *, check_every: int = 2, n_windows: int = 4,
                           seed: int = 0) -> dict:
    """Run the fused driver under the active tracer and split the cold
    first ``als.window`` span (trace + compile + execute) from the
    median warm window.  Requires an active tracer (the harness entry
    installs one); the retrace ledger confirms the cold window is where
    the executable's (only) trace landed."""
    tr = obs_trace.installed()
    if tr is None:
        raise RuntimeError(
            "measure_compile_steady needs an active tracer "
            "(obs.trace.enable/capture)")
    i0 = len(tr.records())
    lstats0 = LEDGER.stats("sweep_block")
    als_device.cpd_als_fused(
        tensor, rank, n_iters=check_every * n_windows, tol=-1.0,
        check_every=check_every, backend=backend, seed=seed)
    lstats1 = LEDGER.stats("sweep_block")
    windows = _span_seconds(tr, i0, "als.window")
    if not windows:
        raise RuntimeError("fused driver emitted no als.window spans")
    cold = windows[0]
    warm = float(np.median(windows[1:])) if len(windows) > 1 else cold
    traces = (None if lstats1["traces"] is None or lstats0["traces"] is None
              else lstats1["traces"] - lstats0["traces"])
    return {
        "cold_window_s": float(cold),
        "steady_window_s": warm,
        "compile_overhead_s": float(max(cold - warm, 0.0)),
        "windows": len(windows),
        "sweep_traces": traces,
    }


# ---------------------------------------------------------------------------
# One dataset end to end
# ---------------------------------------------------------------------------


def calibrate_tensor(
    name: str,
    tensor: SparseTensor,
    *,
    rank: int = 32,
    backends: tuple[str, ...] = ("segment", "coo"),
    predict_fn: Callable[[SparseTensor, int, str], float] | None = None,
    kappa: int = DEFAULT_MESH_KAPPA,
    reps: int = 3,
    imbalance_reps: int = 20,
    seed: int = 0,
) -> list[dict]:
    """Calibrate one Table-3 generator: per-backend predicted-vs-observed
    rows plus one per-mode imbalance row.

    ``predict_fn(tensor, mode, backend) -> seconds`` is the cost model
    under test, injected by the caller (``benchmarks/obs_bench.py``
    wires ``benchmarks/device_model.py`` in; src never imports
    benchmarks).  Without it the prediction fields are None and the row
    is measurement-only.
    """
    rows: list[dict] = []
    N = tensor.nmodes
    for backend in backends:
        measured = measure_mode_seconds(
            tensor, rank, backend, reps=reps, seed=seed, dataset=name)
        per_mode = []
        pred_total = 0.0 if predict_fn is not None else None
        for d in range(N):
            pred = (float(predict_fn(tensor, d, backend))
                    if predict_fn is not None else None)
            if pred is not None:
                pred_total += pred
            per_mode.append({
                "mode": d,
                "predicted_s": pred,
                "measured_s": measured[d],
                "ratio": (pred / measured[d]
                          if pred is not None and measured[d] > 0 else None),
            })
        meas_total = float(sum(measured))
        split = measure_compile_steady(tensor, rank, backend, seed=seed)
        rows.append({
            "name": f"obs/{name}/{backend}",
            "section": "ratio",
            "dataset": name,
            "backend": backend,
            "shape": list(int(s) for s in tensor.shape),
            "nnz": int(tensor.nnz),
            "rank": int(rank),
            "predicted_s": pred_total,
            "measured_s": meas_total,
            "predicted_over_observed": (
                pred_total / meas_total
                if pred_total is not None and meas_total > 0 else None),
            "per_mode": per_mode,
            **split,
        })
    imb = measure_shard_imbalance(tensor, rank, kappa=kappa,
                                  reps=imbalance_reps, seed=seed,
                                  dataset=name)
    rows.append({
        "name": f"obs/{name}/imbalance",
        "section": "imbalance",
        "dataset": name,
        "kappa": int(kappa),
        "shape": list(int(s) for s in tensor.shape),
        "nnz": int(tensor.nnz),
        "per_mode": imb,
        "max_measured_imbalance": max(r["measured_imbalance"] for r in imb),
        "max_nnz_imbalance": max(r["nnz_imbalance"] for r in imb),
    })
    return rows
