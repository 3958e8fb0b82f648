"""Shared benchmark machinery: datasets, baseline MTTKRP formats, timing.

Baseline formats are honest JAX re-implementations of the *algorithmic
idea* of each published baseline (their CUDA kernels cannot run here):

  naive-coo   ParTI-like: unsorted COO, materialized (nnz, R) Khatri-Rao
              intermediate written back per mode, scatter-add updates.
  csf-like    MM-CSF-like: ONE tensor copy sorted for a single mode;
              the other modes run with unsorted scatter-adds (the cost
              MM-CSF pays for avoiding per-mode copies).
  blco-like   BLCO-like: single linearized copy (64-bit packed indices),
              unpacked on the fly each mode, segment-summed after an
              on-device sort per mode (BLCO's conflict resolution).
  ours        mode-specific layouts + adaptive load balancing (the paper).

All run through the SAME CPD-ALS driver so total-execution-time ratios
are apples-to-apples.  CPU wall-time is a proxy for GPU time; the
memory-traffic model (bytes moved per mode) is hardware-independent and
reported alongside.
"""
from __future__ import annotations

import functools
import os
import pathlib
import socket
import subprocess
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import SparseTensor, frostt_like, make_plan, mttkrp
from repro.core.layout import build_mode_layout
from repro.core.load_balance import Scheme
from repro.core.mttkrp import layout_arrays, mttkrp_mode
from repro.kernels import ref as kref

# CI-sized FROSTT stand-ins (same mode-count / dimension ratios, nnz
# scaled; see core.coo.frostt_like).
BENCH_SCALE = 0.04
DATASETS = ("chicago", "enron", "nell-1", "nips", "uber", "vast")
RANK = 32
KAPPA = 82    # the paper's RTX 3090 SM count — kept for comparability

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=1)
def _static_provenance() -> dict:
    def _git(*args: str) -> str:
        try:
            out = subprocess.run(["git", *args], cwd=_REPO_ROOT,
                                 capture_output=True, text=True, timeout=10)
        except (OSError, subprocess.SubprocessError):
            return ""
        return out.stdout.strip() if out.returncode == 0 else ""

    return {
        "git_sha": _git("rev-parse", "HEAD") or "unknown",
        "git_dirty": bool(_git("status", "--porcelain")),
        "host": socket.gethostname(),
        "jax_version": jax.__version__,
        "device": jax.devices()[0].platform,
    }


def provenance() -> dict:
    """Run provenance stamped into every ``BENCH_*.json`` and history
    record: git sha (+ dirty flag), hostname, jax version, device
    platform — cached once per process — plus a fresh UTC timestamp.
    The regression gate keys its cross-machine portability rules off the
    (host, device) pair, so every emitter must carry it."""
    out = dict(_static_provenance())
    out["ts_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    return out


HOST_CHILD_REFUSAL = (
    "runs on forced host devices in a child process, which a process "
    "holding an accelerator must not start; on TPUs run "
    "`python chip_smoke.py --chips 4` for the multi-chip paths")


def holds_accelerator() -> bool:
    """Whether this process runs jax on an accelerator (and so holds it)."""
    return jax.default_backend() != "cpu"


def host_device_env(devices: int) -> dict:
    """Environment for a child process that runs on ``devices`` forced
    host (CPU) devices — jax pins its device count at first init, so a
    virtual mesh needs a fresh interpreter.  The child is pinned to the
    CPU backend, and a process that holds an accelerator may not start
    one at all."""
    if holds_accelerator():
        raise RuntimeError(f"this benchmark {HOST_CHILD_REFUSAL}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(_REPO_ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def load_datasets(scale: float = BENCH_SCALE, include_nell: bool = False):
    names = DATASETS + (("nell-1",) if include_nell else ())
    out = {}
    for n in names:
        sc = scale * (0.1 if n in ("enron", "vast") else 0.01 if n == "nell-1" else 1.0)
        out[n] = frostt_like(n, scale=sc, seed=42)
    return out


# ---------------------------------------------------------------------------
# Baseline engines (mttkrp_fn signatures match core.cpd.cpd_als)
# ---------------------------------------------------------------------------


def engine_ours(plan, factors, mode):
    return mttkrp(plan, factors, mode, backend="segment")


def engine_naive_coo(plan, factors, mode):
    """ParTI-like: unsorted scatter-add with materialized KRP rows."""
    t = plan.tensor
    return kref.mttkrp_coo(
        jnp.asarray(t.indices), jnp.asarray(t.values),
        [jnp.asarray(f) for f in factors], mode, t.shape[mode])


class CSFLikeEngine:
    """One copy sorted for mode 0 only; other modes pay unsorted updates."""

    def __init__(self, tensor: SparseTensor):
        self.layout0 = build_mode_layout(tensor, 0, 1)
        self.tensor = tensor

    def __call__(self, plan, factors, mode):
        if mode == 0:
            lay = self.layout0
            md = tuple(jnp.asarray(a) for a in layout_arrays("segment", lay))
            return mttkrp_mode("segment", 0, md,
                               [jnp.asarray(f) for f in factors],
                               lay.num_rows)
        # other modes: traverse the mode-0-ordered copy, scatter-add
        lay = self.layout0
        idx = jnp.asarray(lay.indices)
        vals = jnp.asarray(lay.values)
        return kref.mttkrp_coo(idx, vals, [jnp.asarray(f) for f in factors],
                               mode, self.tensor.shape[mode])


class BLCOLikeEngine:
    """Single linearized (packed int64) copy; per-mode unpack + sort."""

    def __init__(self, tensor: SparseTensor):
        self.tensor = tensor
        shape = tensor.shape
        self.bits = [int(np.ceil(np.log2(max(2, s)))) for s in shape]
        assert sum(self.bits) <= 63, "tensor too large to linearize in 63b"
        key = np.zeros(tensor.nnz, dtype=np.int64)
        for d in range(tensor.nmodes):
            key = (key << self.bits[d]) | tensor.indices[:, d].astype(np.int64)
        self.packed = jnp.asarray(key)
        self.values = jnp.asarray(tensor.values)

    def _unpack(self):
        cols = []
        shift = 0
        for d in reversed(range(self.tensor.nmodes)):
            mask = (1 << self.bits[d]) - 1
            cols.append((self.packed >> shift) & mask)
            shift += self.bits[d]
        return list(reversed(cols))

    def __call__(self, plan, factors, mode):
        cols = self._unpack()
        idx_d = cols[mode].astype(jnp.int32)
        # BLCO resolves conflicts by sorting nnz by output index per mode.
        order = jnp.argsort(idx_d)
        acc = self.values[order, None].astype(jnp.float32)
        for w in range(self.tensor.nmodes):
            if w == mode:
                continue
            acc = acc * jnp.take(jnp.asarray(factors[w]),
                                 cols[w].astype(jnp.int32)[order], axis=0)
        return jax.ops.segment_sum(
            acc, idx_d[order], num_segments=self.tensor.shape[mode],
            indices_are_sorted=True)


def time_engine(tensor: SparseTensor, engine: Callable, *, rank=RANK,
                iters=3, kappa=KAPPA, scheme=None) -> dict:
    """Time total MTTKRP seconds across all modes x iters inside CPD-ALS."""
    from repro.core.cpd import cpd_als

    plan = make_plan(tensor, kappa, scheme=scheme)
    res = cpd_als(tensor, rank, plan=plan, n_iters=iters, tol=-1.0,
                  mttkrp_fn=engine)
    return {
        "mttkrp_seconds": res.mttkrp_seconds,
        "total_seconds": res.total_seconds,
        "fit": res.fits[-1],
        "iters": res.iters,
    }


def traffic_model(tensor: SparseTensor, fmt: str, *, rank=RANK) -> int:
    """Bytes moved to/from 'global memory' per full all-modes MTTKRP sweep —
    the architecture-independent cost the paper optimizes.  Counts, per
    mode: nnz reads (indices+value), input-factor row gathers, output
    writes, and any intermediate (nnz, R) materialization."""
    N, nnz = tensor.nmodes, tensor.nnz
    R4 = rank * 4
    total = 0
    for d in range(N):
        nnz_bytes = nnz * (4 * N + 4)
        gathers = nnz * (N - 1) * R4
        out = tensor.shape[d] * R4
        if fmt == "ours":
            total += nnz_bytes + gathers + out          # fused: no intermediates
        elif fmt == "naive-coo":
            # materialized KRP intermediate written+read + atomic RMW on out
            total += nnz_bytes + gathers + 2 * nnz * R4 + 2 * nnz * R4
        elif fmt == "csf-like":
            fused = d == 0
            total += nnz_bytes + gathers + (out if fused else 2 * nnz * R4)
        elif fmt == "blco-like":
            # packed key reads + unpack writes + sorted segment pass
            total += nnz * 8 + nnz * 4 * N + gathers + out + nnz * R4
        else:
            raise ValueError(fmt)
    return total
