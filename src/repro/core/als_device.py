"""Device-resident fused CPD-ALS: one jitted XLA computation per sweep.

The paper's thesis is that in the small-tensor regime *overhead*, not
FLOPs, dominates — and the host-loop driver in ``core.cpd`` recreates at
the sweep level exactly the traffic the kernel eliminates at the nnz
level: every mode of every iteration syncs the MTTKRP result to host,
solves the normal equations in numpy, and re-uploads the factor
(~2·N·iters transfers).  This module fuses the entire N-mode sweep —
MTTKRP (segment / pallas / coo backend), gram updates, Cholesky ridge
solve with pinv fallback, column normalization, and the sparse fit — into
a single jit-compiled function with device-carried state:

  * factors / grams / weights never leave the device between iterations;
    the state pytree is donated so XLA reuses the buffers in place.
  * the ``check_every`` iterations between convergence checks run as ONE
    dispatch: a ``lax.scan`` over the sweep body, so the host pays a
    single call per check window instead of one per iteration.  The
    fit (<X, X_hat> + the gram-product model norm) is computed on device
    every sweep: the CP sweep reads <X, X_hat> from the last mode's
    MTTKRP output at O(I_N·R), while method sweeps gather every factor
    at every nonzero (``SweepContext.sparse_fit`` / ``weighted_fit``).
    The host only *fetches* the fit at the window boundary, so host
    syncs drop from 2·N per iteration to 1/k (+1 final
    materialization).  A run whose tolerance is off (``tol <=
    0``) cannot stop early and fetches no fit until the end: its windows
    are dispatched back to back and it syncs once.
    ``CPDResult.host_syncs`` records the actual count.
  * compiled sweep blocks are cached per (backend, nmodes, rank, shapes,
    pallas tiling, block length, method): repeated decompositions of
    same-shape tensors — the serving scenario — pay zero retrace.
    ``sweep_cache_stats()`` exposes the hit/miss counters.

The sweep body itself is *closure-free over tensor data*: runtime arrays
(layout copies, nnz coordinates, fit data) are arguments, never captured
constants.  That is what lets ``repro.serve.batched_engine`` stack B
same-bucket tensors and ``jax.vmap`` the identical sweep into one
batched dispatch (see ``build_sweep_fn``).

Decomposition methods
---------------------
The MTTKRP substrate is method-agnostic: ``build_sweep_fn`` dispatches
the *update rule* through the ``repro.methods`` registry.  ``method=
"cp"`` is the inline unconstrained ALS path below; other methods
(nonnegative HALS, masked/weighted completion, …) receive a
``SweepContext`` carrying the shared MTTKRP primitives, the ridge
solver, and the sparse fit, and return a sweep with the SAME signature —
so every method rides the same executable cache, the same ``lax.scan``
window structure, and the same vmapped batched engine.

Every stage of the sweep is wrapped in ``jax.named_scope`` ("mttkrp",
"solve", "fit", …), and each mode's MTTKRP in ``mode<d>`` below it, so a
profiler trace separates kernel time from solve time and one mode's
layout from another's (``.../mttkrp/mode0/scatter-add``).  The host side
of a fit is in ``obs.trace`` spans, which reach the same profiler trace:
``als.prepare`` (state, mode data and fit data uploads), one
``als.window`` per dispatch with ``als.dispatch`` and, where the run can
stop early, ``als.fetch`` (the fit sync) inside, and ``als.readback``.
``obs.ledger`` counts each fit's dispatches, uploaded bytes and
preparation time.

``core.cpd.cpd_als`` delegates here by default (``engine="fused"``); the
original host loop survives as ``engine="host"`` for benchmarking.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy import linalg as jsla

from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.mttkrp_pallas import mttkrp_pallas, resolve_interpret
from ..obs import clock as obs_clock
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from .coo import SparseTensor
from .cpd import CPDResult
from .mttkrp import MTTKRPPlan, make_plan

_RIDGE_REL = 1e-10

# jax renamed pinv's cutoff kwarg rcond -> rtol; support both.
_PINV_KW = ("rtol" if "rtol" in inspect.signature(jnp.linalg.pinv).parameters
            else "rcond")


def _pinv(a):
    return jnp.linalg.pinv(a, **{_PINV_KW: 1e-10})


def resolve_solver(solver: str) -> str:
    """Resolve 'auto' to the per-backend normal-equations solver (shared
    by the fused, batched, and distributed engines so the same
    configuration can never pick different solvers by front door):
    'cho' (Cholesky — best on TPU/GPU) off-CPU, 'inv' (LU inverse) on
    CPU, where XLA's Cholesky/TriangularSolve custom calls cost ~5 ms
    even at R=16."""
    if solver == "auto":
        solver = "cho" if jax.default_backend() != "cpu" else "inv"
    if solver not in ("cho", "inv"):
        raise ValueError(f"unknown solver {solver!r}")
    return solver


# ---------------------------------------------------------------------------
# MTTKRP substrate (shared by every decomposition method)
# ---------------------------------------------------------------------------


def _mode_scope(mttkrp_fn):
    """Run ``mttkrp_fn(d, ...)`` under ``jax.named_scope(f"mode{d}")``, so
    that the device trace names each mode's operations."""

    @functools.wraps(mttkrp_fn)
    def run(d, *args):
        with jax.named_scope(f"mode{d}"):
            return mttkrp_fn(d, *args)

    return run


def _build_one_mttkrp(backend: str, nmodes: int, shapes: tuple[int, ...],
                      pallas_meta: tuple | None, interpret: bool,
                      axis: str | None,
                      collectives: tuple[str, ...] | None = None):
    """``one_mttkrp(d, mode_data, factors) -> (I_d, R)`` with values baked
    into the mode data (the CP layout contract):

      segment: (idx, rows, vals, row_perm)
      pallas:  (rb_of, first, idx_packed, vals_packed, lrows_packed, row_perm)
      coo:     (indices, values)

    ``collectives`` (distributed segment path only): per-mode choice of
    how partial outputs combine across ``axis`` — "psum" (the default,
    works for both partition schemes) or "gather" (scheme 1 only: each
    device all-gathers just its OWNED row slice and scatters through the
    gathered destination map, moving ~1/kappa of the psum payload; mode
    data widens to ``(idx, rows, vals, row_perm, own_rows, gather_dst)``,
    see ``core.plan.DeviceShards.own_rows``).
    """
    in_modes = [tuple(w for w in range(nmodes) if w != d)
                for d in range(nmodes)]

    @_mode_scope
    def one_mttkrp(d, mode_data, factors):
        """(I_d, R) f32 in ORIGINAL row order, entirely on device."""
        if backend == "segment":
            if (axis is not None and collectives is not None
                    and collectives[d] == "gather"):
                idx, rows, vals, row_perm, own_rows, gather_dst = mode_data
                out = kref.mttkrp_sorted_segments(
                    idx, rows, vals,
                    [factors[w] for w in in_modes[d]], shapes[d]
                )
                # Scheme-1 partials have support only on this device's
                # owned relabeled rows: gather those slices plus their
                # original-row destinations and scatter into a buffer
                # with one dummy row (I_d) absorbing the padding slots.
                own = out[own_rows]                        # (rows_cap, R)
                g_vals = lax.all_gather(own, axis)         # (κ, cap, R)
                g_dst = lax.all_gather(gather_dst, axis)   # (κ, cap)
                full = jnp.zeros((shapes[d] + 1, out.shape[-1]), out.dtype)
                full = full.at[g_dst.reshape(-1)].set(
                    g_vals.reshape(-1, out.shape[-1]))
                return full[: shapes[d]]
            idx, rows, vals, row_perm = mode_data
            out = kref.mttkrp_sorted_segments(
                idx, rows, vals, [factors[w] for w in in_modes[d]], shapes[d]
            )
            if axis is not None:      # combine per-device partials
                out = lax.psum(out, axis)
            return jnp.zeros_like(out).at[row_perm].set(out)
        if backend == "pallas":
            rb_of, first, idxp, valsp, lrowsp, row_perm = mode_data
            nrb, br, tile, rblk = pallas_meta[d]
            out = mttkrp_pallas(
                rb_of, first, idxp, valsp, lrowsp,
                [factors[w] for w in in_modes[d]],
                num_row_blocks=nrb, block_rows=br, tile=tile,
                rank_block=rblk, interpret=interpret,
            )[: shapes[d]]
            if axis is not None:
                out = lax.psum(out, axis)
            return jnp.zeros_like(out).at[row_perm].set(out)
        if backend == "coo":
            indices, values = mode_data
            out = kref.mttkrp_coo(
                indices, values, list(factors), d, shapes[d]
            )
            if axis is not None:
                out = lax.psum(out, axis)
            return out
        raise ValueError(f"unknown backend {backend!r}")

    return one_mttkrp


def _build_valued_mttkrp(backend: str, nmodes: int, shapes: tuple[int, ...],
                         pallas_meta: tuple | None, interpret: bool,
                         axis: str | None):
    """``mttkrp_valued(d, mode_data, factors, vals) -> (I_d, R)``: the
    mask-weighted entry point.  Mode data carries only the STRUCTURAL
    layout arrays; a fresh canonical-order value vector (e.g. the masked
    method's per-sweep residual) is threaded through the same kernels:

      segment: (idx, rows, row_perm, perm)            vals_layout = vals[perm]
      pallas:  (rb_of, first, idx_packed, lrows_packed,
                row_perm, perm, val_scatter)           scatter into the slabs
      coo:     (indices,)                              canonical order already

    With ``axis`` (the distributed shard_map path, segment backend only)
    the contract changes: mode data is the device-local structural shard
    ``(idx, rows, row_perm)`` and ``vals`` arrives in LAYOUT-SHARD order
    (each device evaluates its residual at its own shard's coordinates —
    see ``methods.masked``), so no canonical->layout permutation exists;
    the partial outputs are ``psum``-combined over the axis.
    """
    in_modes = [tuple(w for w in range(nmodes) if w != d)
                for d in range(nmodes)]

    if axis is not None:
        if backend != "segment":
            raise NotImplementedError(
                "the distributed valued MTTKRP runs on the segment backend "
                f"(shard_map path), got {backend!r}")

        @_mode_scope
        def mttkrp_valued_dist(d, mode_data, factors, vals):
            idx, rows, row_perm = mode_data
            out = kref.mttkrp_sorted_segments(
                idx, rows, vals, [factors[w] for w in in_modes[d]], shapes[d]
            )
            out = lax.psum(out, axis)
            return jnp.zeros_like(out).at[row_perm].set(out)

        return mttkrp_valued_dist

    @_mode_scope
    def mttkrp_valued(d, mode_data, factors, vals):
        if backend == "segment":
            idx, rows, row_perm, perm = mode_data
            out = kref.mttkrp_sorted_segments(
                idx, rows, vals[perm],
                [factors[w] for w in in_modes[d]], shapes[d]
            )
            return jnp.zeros_like(out).at[row_perm].set(out)
        if backend == "pallas":
            rb_of, first, idxp, lrowsp, row_perm, perm, scatter = mode_data
            nrb, br, tile, rblk = pallas_meta[d]
            valsp = jnp.zeros((1, idxp.shape[-1]), jnp.float32)
            valsp = valsp.at[0, scatter].set(vals[perm])
            out = mttkrp_pallas(
                rb_of, first, idxp, valsp, lrowsp,
                [factors[w] for w in in_modes[d]],
                num_row_blocks=nrb, block_rows=br, tile=tile,
                rank_block=rblk, interpret=interpret,
            )[: shapes[d]]
            return jnp.zeros_like(out).at[row_perm].set(out)
        if backend == "coo":
            (indices,) = mode_data
            return kref.mttkrp_coo(
                indices, vals, list(factors), d, shapes[d]
            )
        raise ValueError(f"unknown backend {backend!r}")

    return mttkrp_valued


def _hadamard_grams(grams, rank: int, exclude: int | None = None):
    V = jnp.ones((rank, rank), jnp.float32)
    for w, g in enumerate(grams):
        if w != exclude:
            V = V * g
    return V


def _build_solver(rank: int, solver: str, fallback: str):
    """``solve(M, V) -> Yd``: ridge-regularized normal-equations solve with
    the optional pinv rescue — the exact CP solve, shared with the masked
    method so both produce the same numerics."""
    eye = jnp.eye(rank, dtype=jnp.float32)

    def solve(M, V):
        ridge = _RIDGE_REL * jnp.maximum(jnp.trace(V) / rank, 1.0)
        Vr = V + ridge * eye
        # Ridge solve; pinv fallback if the factorization NaNs out
        # (V near-singular beyond what the ridge absorbs).  "cho" is
        # the Cholesky path (best on TPU/GPU); "inv" multiplies by the
        # explicit inverse — XLA's CPU Cholesky/TriangularSolve custom
        # calls cost ~5 ms even at R=16, an order of magnitude more
        # than the LU inverse, so "auto" picks per backend.
        if solver == "cho":
            Yd = jsla.cho_solve(jsla.cho_factor(Vr), M.T).T
        else:
            Yd = M @ jnp.linalg.inv(Vr)
        # lax.cond (not jnp.where) so the SVD-based pinv only runs on
        # the rare singular miss, never in the hot path.  (Under vmap
        # the cond lowers to a select and both branches run — the
        # batched engine therefore builds fallback='none' sweeps and
        # hoists one batch-level all-finite cond around the window.)
        if fallback == "cond":
            Yd = lax.cond(
                jnp.all(jnp.isfinite(Yd)),
                lambda yd, m, v: yd,
                lambda yd, m, v: m @ _pinv(v),
                Yd, M, Vr,
            )
        return Yd

    return solve


def normalize_columns(Yd):
    """Column-normalize, guarding dead columns; returns (Yd, lam)."""
    lam = jnp.linalg.norm(Yd, axis=0)
    lam = jnp.where(lam > 1e-12, lam, 1.0)
    return Yd / lam, lam


def _fit_from_innerprod(ip, grams, weights, norm_x_sq, rank: int):
    """``1 - ||X - X_hat|| / ||X||`` from ``ip = <X, X_hat>``, the model
    norm ``weights' (*_d A_d'A_d) weights`` from the grams, and
    ``norm_x_sq``: the tail every unweighted fit shares."""
    V = _hadamard_grams(grams, rank)
    model_sq = weights @ V @ weights
    resid_sq = jnp.maximum(norm_x_sq - 2.0 * ip + model_sq, 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / jnp.maximum(
        jnp.sqrt(norm_x_sq), 1e-12)


def _build_sparse_fit(nmodes: int, rank: int, axis: str | None):
    """On-device sparse fit (jnp ports of cpd._innerprod_sparse /
    cpd._model_norm_sq): no dense reconstruction, no host round-trip.
    Gathers every factor at every nonzero for ``<X, X_hat>``: method
    sweeps use it; the inline CP sweep reads ``<X, X_hat>`` from its last
    mode's MTTKRP (``build_sweep_fn``).
    Zero-valued padding entries (serve.buckets) contribute exactly +0.0
    to both the Hadamard accumulation and the inner product."""

    def sparse_fit(factors, grams, weights, fit_data):
        indices, values, norm_x_sq = fit_data
        _LEDGER.count("sweep_fit", gather=1)
        acc = jnp.ones((values.shape[0], rank), jnp.float32)
        for d in range(nmodes):
            acc = acc * factors[d][indices[:, d]]
        ip = values @ (acc @ weights)
        if axis is not None:          # nnz are sharded across devices
            ip = lax.psum(ip, axis)
        return _fit_from_innerprod(ip, grams, weights, norm_x_sq, rank)

    return sparse_fit


def _build_weighted_fit(nmodes: int, rank: int, axis: str | None):
    """Observed-only weighted fit shared by the masked method across every
    execution path:  ``1 - sqrt(sum_e w_e (x_e - model_e)^2) /
    sqrt(sum_e w_e x_e^2)``.  ``fit_data = (indices, values,
    entry_weights, weighted_norm_sq)``; weight-0 entries (nnz padding, or
    entries the caller masked out) contribute exactly +0.0.  Under
    ``axis`` the nnz are device shards and the residual mass psums."""

    def weighted_fit(factors, weights, fit_data):
        indices, values, ew, norm_x_sq = fit_data
        _LEDGER.count("sweep_fit", gather=1)
        acc = jnp.ones((values.shape[0], rank), jnp.float32)
        for d in range(nmodes):
            acc = acc * factors[d][indices[:, d]]
        resid = values - acc @ weights
        resid_sq = jnp.sum(ew * resid * resid)
        if axis is not None:
            resid_sq = lax.psum(resid_sq, axis)
        return 1.0 - jnp.sqrt(resid_sq) / jnp.maximum(
            jnp.sqrt(norm_x_sq), 1e-12)

    return weighted_fit


def validate_entry_weights(nnz: int, weights) -> np.ndarray:
    """Normalize a front-door per-entry weight vector: (nnz,) f32,
    finite, nonnegative.  Shared by every front door (sequential fused,
    batched service, distributed) so they can never disagree on what a
    legal weight vector is."""
    w = np.asarray(weights, dtype=np.float32).reshape(-1)
    if w.shape[0] != nnz:
        raise ValueError(
            f"entry weights must align with the nnz list: got {w.shape[0]} "
            f"weights for {nnz} nonzeros")
    if not np.all(np.isfinite(w)):
        raise ValueError("entry weights must be finite")
    if w.size and float(w.min()) < 0.0:
        raise ValueError("entry weights must be nonnegative")
    return w


def normalize_entry_weights(w: np.ndarray) -> np.ndarray:
    """EM stability normalization, applied by every weighted front door
    (sequential, batched, distributed — so they can never disagree): the
    masked method's filled-tensor update is a majorizer only for weights
    in [0, 1], while the weighted objective — argmin AND reported fit —
    is invariant under positive rescaling of the whole vector.  Dividing
    by ``max(1, w.max())`` therefore changes nothing the caller can
    observe except that the iteration is guaranteed stable.  Vectors
    already in [0, 1] pass through untouched (bit-exactly), and the map
    is idempotent."""
    m = float(w.max()) if w.size else 0.0
    return (w / np.float32(m)).astype(np.float32) if m > 1.0 else w


@dataclasses.dataclass(frozen=True)
class SweepContext:
    """Everything a decomposition method needs to build its sweep on the
    shared substrate.  ``repro.methods`` specs receive this and return
    ``sweep(state, mode_data_all, fit_data) -> (state, fit)`` — the same
    contract as the inline CP sweep, so method sweeps drop into the
    sequential scan block, the vmapped batched engine, and the executable
    cache unchanged."""

    backend: str
    nmodes: int
    rank: int
    shapes: tuple[int, ...]
    solver: str
    fallback: str
    axis: str | None
    one_mttkrp: Callable      # (d, mode_data, factors) -> (I_d, R)
    mttkrp_valued: Callable   # (d, mode_data, factors, vals) -> (I_d, R)
    solve: Callable           # (M, V) -> Yd  (ridge + pinv rescue)
    normalize: Callable       # (Yd) -> (Yd, lam)  (dead-column guard)
    # (factors, grams, weights, fit_data) -> fit: gathers every factor
    # at every nonzero (nncp).  The inline CP sweep reads <X, X_hat>
    # from its last mode's MTTKRP instead.
    sparse_fit: Callable
    weighted_fit: Callable    # (factors, weights, fit_data4) -> fit
    hadamard: Callable        # (grams, exclude=None) -> (R, R)


# ---------------------------------------------------------------------------
# Closure-free sweep builder (shared by the sequential and batched engines)
# ---------------------------------------------------------------------------


def _f32_matmuls(sweep):
    """Trace ``sweep`` with f32 matmul precision.  A TPU's default f32
    matmul is a single bf16 pass, which would round the grams, the
    normal-equations solve and the fit to bf16 and drift the fits away
    from the float64 host loop; on the CPU the setting changes nothing."""

    @functools.wraps(sweep)
    def run(*args):
        with jax.default_matmul_precision("float32"):
            return sweep(*args)

    return run


@functools.lru_cache(maxsize=None)
def build_sweep_fn(backend: str, nmodes: int, rank: int,
                   shapes: tuple[int, ...],
                   pallas_meta: tuple | None,
                   interpret: bool, solver: str,
                   axis: str | None = None,
                   fallback: str = "cond",
                   method: str = "cp",
                   collectives: tuple[str, ...] | None = None):
    """Build (and cache) the *pure* one-full-sweep function for a static
    configuration: ``sweep(state, mode_data_all, fit_data) -> (state, fit)``.

    All runtime data (layout arrays, nnz coordinates, fit inputs) are
    arguments — the function closes over nothing but static ints — so it
    can be jitted directly (sequential engine), ``jax.vmap``-ed over a
    stacked leading axis (``serve.batched_engine``), or run inside
    ``shard_map`` (``core.distributed``): every tensor of the same
    (shape, nnz-bucket) class shares this one function object.

    ``axis``: a mesh axis name — mode data and fit data are then
    device-local shards and the sweep ``psum``s the partial MTTKRP output
    over that axis (the distributed path); the CP fit, read from that
    combined output, needs no collective of its own, while a method's
    gathered fit ``psum``s its inner product.
    ``fallback``: 'cond' guards the solve with the pinv rescue (the
    sequential default); 'none' omits it so a batch-level all-finite cond
    can be hoisted AROUND the whole window (``serve.batched_engine``) —
    under vmap the per-element cond would lower to a select that always
    pays the small-R SVD.
    ``method``: which decomposition method's update rule runs on the
    substrate — 'cp' is the inline path below; anything else resolves
    through the ``repro.methods`` registry.
    ``collectives``: per-mode cross-device combine for the distributed
    segment path ("psum" | "gather"); see ``_build_one_mttkrp``.
    """
    if fallback not in ("cond", "none"):
        raise ValueError(f"unknown fallback {fallback!r}")
    if collectives is not None:
        if axis is None or backend != "segment":
            raise ValueError(
                "per-mode collectives apply to the distributed segment "
                "path only (axis set, backend='segment')")
        if len(collectives) != nmodes or any(
                c not in ("psum", "gather") for c in collectives):
            raise ValueError(f"bad collectives {collectives!r}")

    one_mttkrp = _build_one_mttkrp(backend, nmodes, shapes, pallas_meta,
                                   interpret, axis, collectives)
    solve = _build_solver(rank, solver, fallback)

    if method != "cp":
        from ..methods import get_method   # lazy: core must import clean

        spec = get_method(method)
        if spec.build_sweep is None:
            raise ValueError(
                f"method {method!r} has no sweep builder (stateful methods "
                f"drive the substrate through their session API)")
        mttkrp_valued = (
            _build_valued_mttkrp(backend, nmodes, shapes, pallas_meta,
                                 interpret, axis)
            if (axis is None or backend == "segment") else None)
        ctx = SweepContext(
            backend=backend, nmodes=nmodes, rank=rank, shapes=shapes,
            solver=solver, fallback=fallback, axis=axis,
            one_mttkrp=one_mttkrp, mttkrp_valued=mttkrp_valued,
            solve=solve, normalize=normalize_columns,
            sparse_fit=_build_sparse_fit(nmodes, rank, axis),
            weighted_fit=_build_weighted_fit(nmodes, rank, axis),
            hadamard=functools.partial(_hadamard_grams, rank=rank),
        )
        return _f32_matmuls(spec.build_sweep(ctx))

    @_f32_matmuls
    def sweep(state, mode_data_all, fit_data):
        factors, grams, weights = list(state[0]), list(state[1]), state[2]
        for d in range(nmodes):
            with jax.named_scope("mttkrp"):
                M = one_mttkrp(d, mode_data_all[d], factors)
            with jax.named_scope("solve"):
                V = _hadamard_grams(grams, rank, exclude=d)
                Yd = solve(M, V)
                Yd, lam = normalize_columns(Yd)
            factors[d] = Yd
            grams[d] = Yd.T @ Yd
            weights = lam
        # The last mode's M was computed against factors 0..N-2 as they
        # leave this sweep, so <X, X_hat> = sum_{i,r} M * A_{N-1} * lam.
        # M is already combined across ``axis``: the product is global.
        # The COO list in ``fit_data`` is not read.
        _LEDGER.count("sweep_fit", mttkrp=1)
        _, _, norm_x_sq = fit_data
        with jax.named_scope("fit"):
            ip = jnp.sum(M * factors[-1], axis=0) @ weights
            fit = _fit_from_innerprod(ip, grams, weights, norm_x_sq, rank)
        return (tuple(factors), tuple(grams), weights), fit

    return sweep


# ---------------------------------------------------------------------------
# Compiled sweep-block cache (lax.scan over one check window)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_sweep_block(backend: str, nmodes: int, rank: int,
                       shapes: tuple[int, ...],
                       pallas_meta: tuple | None,
                       interpret: bool, donate: bool, solver: str,
                       block: int, method: str = "cp"):
    """Jitted ``lax.scan`` of ``block`` consecutive sweeps: the whole
    check window is ONE dispatch.  Returns the carried state plus the
    per-iteration fit vector ``(block,)`` so the fit history stays
    complete.

    Each built block registers in the obs retrace ledger: the lru key
    here deliberately omits nnz (jit re-specializes per array shape
    inside one cache entry), so lru hits/misses alone cannot see the
    retrace a NOVEL nnz causes — the ledger's per-executable trace
    counts can."""
    sweep = build_sweep_fn(backend, nmodes, rank, shapes, pallas_meta,
                           interpret, solver, method=method)

    def run_block(state, mode_data_all, fit_data):
        def body(st, _):
            return sweep(st, mode_data_all, fit_data)

        state, fits = lax.scan(body, state, xs=None, length=block)
        return state, fits

    fn = jax.jit(run_block, donate_argnums=(0,) if donate else ())
    return _LEDGER.register(
        "sweep_block",
        (backend, nmodes, rank, shapes, "block", block, "method", method),
        fn)


def sweep_cache_stats():
    """(hits, misses, currsize) of the compiled sweep-block cache — the
    probe for 'repeated same-shape decompositions pay zero retrace'.
    ``runtime.ALSRunner`` records the per-request delta so retrace-induced
    stragglers are distinguishable from contention stragglers."""
    info = _build_sweep_block.cache_info()
    return {"hits": info.hits, "misses": info.misses,
            "currsize": info.currsize}


def sweep_trace_stats():
    """Total TRACES across all jitted sweep blocks — the probe the lru
    stats above cannot provide: nnz is not part of the lru key (jit
    re-specializes per argument shape inside one entry), so a stream of
    ever-novel nnz counts shows lru hits while silently retracing every
    call.  ``traces`` counts actual specializations (as a delta since
    the last ledger ``reset()`` — an autouse test fixture resets, so
    assertions cannot leak across tests); a zero-retrace streaming
    increment leaves it unchanged.  Best-effort: jax's ``_cache_size``
    is version-private, so absent introspection support this reports
    blocks only (traces=None).

    This is now a view over ``repro.obs.ledger.LEDGER`` (which also
    covers the batched, pod and distributed executables — query those
    kinds there); the old module-global registry is gone.
    """
    s = _LEDGER.stats("sweep_block")
    return {"blocks": s["blocks"], "traces": s["traces"]}


def _collect_mode_data(plan: MTTKRPPlan, backend: str, rank: int):
    """Per-mode device arrays (cached on the plan) + static pallas tiling."""
    N = plan.tensor.nmodes
    if backend == "segment":
        return tuple(plan.device_arrays(d) for d in range(N)), None
    if backend == "pallas":
        datas, metas = [], []
        for d in range(N):
            packed = plan.packed(d)
            mp = plan.mode_plan(d, rank)    # core.plan decides rank_block
            dev = plan.device_packed(d)
            datas.append(dev + (jnp.asarray(plan.layouts[d].row_perm),))
            metas.append((packed.num_row_blocks, packed.block_rows,
                          packed.tile, mp.rank_block))
        return tuple(datas), tuple(metas)
    if backend == "coo":
        coo = plan.device_coo()
        return tuple(coo for _ in range(N)), None
    raise ValueError(f"unknown backend {backend!r}")


def collect_structural_mode_data(plan: MTTKRPPlan, backend: str, rank: int):
    """Mode data for the *valued* MTTKRP contract (see
    ``_build_valued_mttkrp``): structural layout arrays plus the
    canonical->layout permutation (and canonical->slab scatter for
    pallas), NO baked values.  The masked method collects through here."""
    N = plan.tensor.nmodes
    if backend == "segment":
        datas = []
        for d in range(N):
            lay = plan.layouts[d]
            im = lay.input_modes()
            datas.append((
                jnp.asarray(lay.indices[:, im]),
                jnp.asarray(lay.rows),
                jnp.asarray(lay.row_perm),
                jnp.asarray(lay.perm.astype(np.int32)),
            ))
        return tuple(datas), None
    if backend == "pallas":
        datas, metas = [], []
        for d in range(N):
            packed = plan.packed(d)
            mp = plan.mode_plan(d, rank)
            lay = plan.layouts[d]
            datas.append((
                jnp.asarray(packed.rb_of),
                jnp.asarray(packed.first),
                jnp.asarray(packed.idx_packed),
                jnp.asarray(packed.lrows_packed),
                jnp.asarray(lay.row_perm),
                jnp.asarray(lay.perm.astype(np.int32)),
                jnp.asarray(packed.val_scatter),
            ))
            metas.append((packed.num_row_blocks, packed.block_rows,
                          packed.tile, mp.rank_block))
        return tuple(datas), tuple(metas)
    if backend == "coo":
        idx = jnp.asarray(plan.tensor.indices)
        return tuple((idx,) for _ in range(N)), None
    raise ValueError(f"unknown backend {backend!r}")


def init_state_host(tensor_shape, rank: int, seed: int):
    """Host-side (pure numpy) random init shared by every engine: same
    seed => same starting point for the host loop, the fused engine, and
    the batched engine.  Kept on host so the serving path can stack B of
    these and upload ONE array per state leaf instead of paying 2N+1 tiny
    transfers plus N gram matmul dispatches per tensor."""
    rng = np.random.default_rng(seed)
    factors = tuple(
        rng.standard_normal((I, rank)).astype(np.float32)
        for I in tensor_shape
    )
    grams = tuple(F.T @ F for F in factors)
    weights = np.ones((rank,), np.float32)
    return (factors, grams, weights)


def state_from_factors(factors, weights=None):
    """Host state tuple from explicit (e.g. previously fitted) factors:
    the warm-start entry the streaming method folds increments through.
    Grams are recomputed so the state is always self-consistent."""
    factors = tuple(np.asarray(F, dtype=np.float32) for F in factors)
    grams = tuple(F.T @ F for F in factors)
    rank = factors[0].shape[1]
    if weights is None:
        weights = np.ones((rank,), np.float32)
    return (factors, grams, np.asarray(weights, dtype=np.float32))


def init_state(tensor_shape, rank: int, seed: int):
    """Device-resident init for the sequential fused engine."""
    factors, grams, weights = init_state_host(tensor_shape, rank, seed)
    return (tuple(jnp.asarray(F) for F in factors),
            tuple(jnp.asarray(G) for G in grams),
            jnp.asarray(weights))


def _method_spec(method: str):
    if method == "cp":
        return None
    from ..methods import get_method

    spec = get_method(method)
    if spec.build_sweep is None:
        raise ValueError(
            f"method {method!r} is stateful; drive it through its session "
            f"API (e.g. repro.methods.StreamingCP / ALSRunner.open_stream)")
    return spec


def _host_state_to_device(state):
    return (tuple(jnp.asarray(F) for F in state[0]),
            tuple(jnp.asarray(G) for G in state[1]),
            jnp.asarray(state[2]))


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def uploaded_nbytes(tree, kept=()) -> int:
    """Bytes of the distinct device arrays in ``tree`` that are not among
    ``kept`` (uploaded by an earlier call and cached)."""
    skip = {id(x) for x in jax.tree_util.tree_leaves(kept)}
    fresh = {id(x): int(x.nbytes) for x in jax.tree_util.tree_leaves(tree)
             if id(x) not in skip}
    return sum(fresh.values())


@dataclasses.dataclass
class _PreparedFit:
    """What ``cpd_als_fused`` uploads and builds before its first
    dispatch."""

    state: tuple
    mode_data_all: tuple
    fit_data: tuple
    sweep_k: Callable | None        # the check-window block
    sweep_rem: Callable | None      # the shorter last block
    h2d_bytes: int
    hbm_gather_bytes: int           # per sweep (Pallas backend; else 0)


def _prepare_fit(tensor, rank, spec, *, plan, kappa, n_iters, seed,
                 backend, check_every, interpret, donate, solver, method,
                 init_state, weights) -> _PreparedFit:
    N = tensor.nmodes
    if init_state is not None:
        state = _host_state_to_device(init_state)
    elif spec is not None and spec.init_state_host is not None:
        state = _host_state_to_device(
            spec.init_state_host(tensor.shape, rank, seed))
    else:
        state = _host_state_to_device(
            init_state_host(tensor.shape, rank, seed))

    structural = spec is not None and spec.valued_mode_data
    if plan is None and backend == "coo":
        # The coo backend needs no mode-specific layouts: skip the host-side
        # preprocessing (per-mode sorts) entirely and upload the raw COO.
        idx = jnp.asarray(tensor.indices)
        if structural:
            mode_data_all, pallas_meta = tuple((idx,) for _ in range(N)), None
        else:
            coo = (idx, jnp.asarray(tensor.values.astype(np.float32)))
            mode_data_all, pallas_meta = tuple(coo for _ in range(N)), None
    else:
        if plan is None:
            plan = make_plan(tensor, kappa)
        if structural:
            mode_data_all, pallas_meta = collect_structural_mode_data(
                plan, backend, rank)
        else:
            mode_data_all, pallas_meta = _collect_mode_data(
                plan, backend, rank)
    if spec is not None and spec.make_fit_data is not None:
        fit_data = spec.make_fit_data(tensor, weights)
    else:
        norm_x_sq = tensor.norm() ** 2
        fit_data = (
            jnp.asarray(tensor.indices),
            jnp.asarray(tensor.values.astype(np.float32)),
            jnp.asarray(norm_x_sq, jnp.float32),
        )

    shapes = tuple(int(s) for s in tensor.shape)
    n_blocks, rem = divmod(n_iters, check_every)
    sweep_k = _build_sweep_block(
        backend, N, rank, shapes, pallas_meta, interpret, donate,
        solver, check_every, method,
    ) if n_blocks else None
    sweep_rem = _build_sweep_block(
        backend, N, rank, shapes, pallas_meta, interpret, donate,
        solver, rem, method,
    ) if rem else None
    gather = 0
    if backend == "pallas":
        for d in range(N):
            rows = [shapes[w] for w in plan.layouts[d].input_modes()]
            gather += kops.hbm_gather_bytes(plan.packed(d), rows, rank,
                                            pallas_meta[d][3])
    # The plan's cached arrays are counted by the plan, on first upload.
    kept = plan.device_cache() if plan is not None else ()
    return _PreparedFit(
        state, mode_data_all, fit_data, sweep_k, sweep_rem,
        uploaded_nbytes((state, mode_data_all, fit_data), kept), gather)


def _read_back(fits_dev, state):
    """The fit history, factors and weights on the host: the run's final
    materialization."""
    fits = [float(f) for blk in jax.device_get(fits_dev) for f in blk]
    return (fits, [np.asarray(F) for F in state[0]],
            np.asarray(state[2], dtype=np.float64))


def cpd_als_fused(
    tensor: SparseTensor,
    rank: int,
    *,
    plan: MTTKRPPlan | None = None,
    kappa: int = 1,
    n_iters: int = 25,
    tol: float = 1e-5,
    seed: int = 0,
    backend: str = "segment",
    check_every: int = 1,
    interpret: bool | None = None,
    donate: bool | None = None,
    solver: str = "auto",
    method: str = "cp",
    init_state: tuple | None = None,
    weights: np.ndarray | None = None,
    verbose: bool = False,
) -> CPDResult:
    """Device-resident CPD-ALS.  Same initialization and update order as the
    host-loop ``cpd_als`` (identical seed ⇒ matching trajectories up to f32
    vs f64 solver precision), but every ``check_every``-iteration window
    runs as one compiled ``lax.scan`` dispatch and the host syncs only at
    window boundaries.

    ``method`` selects the update rule (see ``repro.methods``); every
    method shares this driver, the window scan, and the executable cache.
    ``init_state`` (a host state tuple, e.g. from ``state_from_factors``)
    warm-starts from existing factors instead of the seeded random init —
    the streaming method's incremental-fold entry.
    ``weights`` — per-entry observation weights in canonical COO order
    (fractional confidences; weight 0 = treat the entry as unobserved).
    Only weighted-fit methods ('masked') accept them; they flow into the
    method's fit data, never into the structural layouts, so weighted and
    unweighted requests share every packed artifact and executable.

    ``CPDResult.mttkrp_seconds`` stays 0.0: the MTTKRP's device time is
    in a profiler trace, under the ``mttkrp/mode<d>`` scopes.
    """
    t_start = obs_clock.now()
    tr = obs_trace.active()
    check_every = max(1, int(check_every))
    spec = _method_spec(method)
    if weights is not None:
        if spec is None or not spec.weighted_fit:
            raise ValueError(
                f"per-entry weights require a weighted-fit method "
                f"(e.g. 'masked'), got method={method!r}")
        weights = normalize_entry_weights(
            validate_entry_weights(tensor.nnz, weights))
    if donate is None:
        # Buffer donation is a no-op (with a warning) on CPU.
        donate = jax.default_backend() != "cpu"
    kw = dict(plan=plan, kappa=kappa, n_iters=n_iters, seed=seed,
              backend=backend, check_every=check_every,
              interpret=resolve_interpret(interpret), donate=bool(donate),
              solver=resolve_solver(solver), method=method,
              init_state=init_state, weights=weights)
    if tr is None:
        prep = _prepare_fit(tensor, rank, spec, **kw)
    else:
        with tr.span("als.prepare", cat="als") as sp:
            prep = _prepare_fit(tensor, rank, spec, **kw)
            sp.set(h2d_bytes=prep.h2d_bytes)
    prepare_s = obs_clock.now() - t_start
    state, mode_data_all, fit_data = (prep.state, prep.mode_data_all,
                                      prep.fit_data)

    n_blocks, rem = divmod(n_iters, check_every)
    # No fit change is below a tolerance <= 0, so such a run cannot stop
    # early: its windows go out back to back, with no fit fetched between
    # them, and the device never waits on a host round trip.
    fetch = tol > 0 or verbose
    fits_dev: list = []
    host_syncs = 0
    last_fit = -np.inf
    it = 0
    for b in range(n_blocks + (1 if rem else 0)):
        k = check_every if b < n_blocks else rem
        fn = prep.sweep_k if b < n_blocks else prep.sweep_rem
        # Dispatch + the window-boundary fit sync, the per-window hot
        # path: the tracing-disabled branch reads two globals, probes the
        # profiler and allocates nothing (enforced by
        # tests/obs/test_trace.py).
        if tr is None:
            state, fits_blk = fn(state, mode_data_all, fit_data)
            if fetch:
                f = float(fits_blk[-1])         # the only in-loop host sync
        else:
            with tr.span("als.window", cat="als", backend=backend,
                         method=method, window=b, sweeps=k):
                with tr.span("als.dispatch", cat="als"):
                    state, fits_blk = fn(state, mode_data_all, fit_data)
                if fetch:
                    with tr.span("als.fetch", cat="als"):
                        f = float(fits_blk[-1])  # the only in-loop sync
        fits_dev.append(fits_blk)
        it += k
        if not fetch:
            continue
        host_syncs += 1
        if verbose:
            print(f"  ALS iter {it:3d}: fit={f:.6f} ({method}/fused)")
        if abs(f - last_fit) < tol:
            break
        last_fit = f

    host_syncs += 1                             # final materialization
    # One batched device_get for the whole run (not a fetch per window),
    # so host_syncs honestly reflects the transfer count.
    if tr is None:
        fits, factors, lam = _read_back(fits_dev, state)
    else:
        with tr.span("als.readback", cat="als"):
            fits, factors, lam = _read_back(fits_dev, state)
    _LEDGER.count("sweep_block", dispatches=len(fits_dev),
                  h2d_bytes=prep.h2d_bytes, prepare_s=prepare_s)
    if prep.hbm_gather_bytes:
        _LEDGER.count("sweep_block",
                      hbm_gather_bytes=it * prep.hbm_gather_bytes)

    return CPDResult(
        factors=factors,
        weights=lam,
        fits=fits,
        iters=it,
        mttkrp_seconds=0.0,
        total_seconds=obs_clock.now() - t_start,
        host_syncs=host_syncs,
        engine="fused",
        method=method,
    )
