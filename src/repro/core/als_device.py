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

The MTTKRP itself is ``core.mttkrp.mttkrp_mode``, the one dispatch
every engine runs; this module only wraps it in the per-mode scope and,
under a mesh axis, hands it the cross-device combine.  The host window
loop (``_run_fit``) serves this module's ``cpd_als_fused`` and
``core.distributed.cpd_als_distributed`` alike.

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
from ..kernels.mttkrp_pallas import resolve_interpret
from ..kernels.ref import cp_model_at_coords
from ..obs import clock as obs_clock
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from .coo import SparseTensor
from .cpd import CPDResult
from .mttkrp import (MTTKRPPlan, collect_mode_data, layout_values,
                     make_plan, mttkrp_mode, owned_rows_combine,
                     psum_combine)

# Value-baked mode data for a plan, under the name the benchmark's own
# code reads.
_collect_mode_data = collect_mode_data

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


def _build_mttkrp(backend: str, shapes: tuple[int, ...],
                  pallas_meta: tuple | None, interpret: bool,
                  axis: str | None,
                  collectives: tuple[str, ...] | None = None):
    """``mttkrp(d, mode_data, factors, vals=None) -> (I_d, R)``: the
    sweep's MTTKRP, ``core.mttkrp.mttkrp_mode`` under
    ``jax.named_scope(f"mode{d}")``, so that the device trace names each
    mode's operations.

    Without ``vals`` the values are baked into the mode data (the CP
    layout contract).  With ``vals`` the mode data is structural
    (``core.mttkrp.MODE_FIELDS``) and ``vals`` a fresh canonical-order
    value vector (e.g. the masked method's per-sweep residual), which is
    permuted or scattered into layout form first.

    With ``axis`` (the distributed shard_map path) mode data is the
    device-local shard and the partial outputs combine across ``axis``:
    by ``psum``, or, for a mode whose ``collectives`` entry is "gather"
    (scheme 1 only), by all-gathering each device's owned row slice,
    whose indices ride as two extra arrays after the value-baked segment
    fields (see ``core.plan.DeviceShards.own_rows``).  Valued calls
    receive ``vals`` in layout-shard order already (each device evaluates
    its residual at its own shard's coordinates, see ``methods.masked``).
    """

    def combine(d, mode_data):
        if axis is None:
            return None
        if collectives is not None and collectives[d] == "gather":
            return owned_rows_combine(axis, *mode_data[4:6], shapes[d])
        return psum_combine(axis)

    def mttkrp(d, mode_data, factors, vals=None):
        with jax.named_scope(f"mode{d}"):
            if vals is not None and axis is None:
                vals = layout_values(backend, mode_data, vals)
            return mttkrp_mode(
                backend, d, mode_data, factors, shapes[d],
                meta=None if pallas_meta is None else pallas_meta[d],
                interpret=interpret, vals=vals,
                combine=combine(d, mode_data))

    return mttkrp


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


def _build_sparse_fit(rank: int, axis: str | None):
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
        ip = values @ cp_model_at_coords(indices, factors, weights)
        if axis is not None:          # nnz are sharded across devices
            ip = lax.psum(ip, axis)
        return _fit_from_innerprod(ip, grams, weights, norm_x_sq, rank)

    return sparse_fit


def _build_weighted_fit(axis: str | None):
    """Observed-only weighted fit shared by the masked method across every
    execution path:  ``1 - sqrt(sum_e w_e (x_e - model_e)^2) /
    sqrt(sum_e w_e x_e^2)``.  ``fit_data = (indices, values,
    entry_weights, weighted_norm_sq)``; weight-0 entries (nnz padding, or
    entries the caller masked out) contribute exactly +0.0.  Under
    ``axis`` the nnz are device shards and the residual mass psums."""

    def weighted_fit(factors, weights, fit_data):
        indices, values, ew, norm_x_sq = fit_data
        _LEDGER.count("sweep_fit", gather=1)
        resid = values - cp_model_at_coords(indices, factors, weights)
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


def _checked_entry_weights(spec, method: str, nnz: int, weights):
    """A front door's ``weights``, validated and normalized; refused for
    a method without a weighted fit.  ``None`` stays ``None``."""
    if weights is None:
        return None
    if spec is None or not spec.weighted_fit:
        raise ValueError(
            f"per-entry weights require a weighted-fit method "
            f"(e.g. 'masked'), got method={method!r}")
    return normalize_entry_weights(validate_entry_weights(nnz, weights))


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
    # (d, mode_data, factors[, vals]) -> (I_d, R): value-baked mode data
    # without vals, structural mode data with them (``_build_mttkrp``).
    one_mttkrp: Callable
    solve: Callable           # (M, V) -> Yd  (ridge + pinv rescue)
    normalize: Callable       # (Yd) -> (Yd, lam)  (dead-column guard)
    # (factors, grams, weights, fit_data) -> fit: gathers every factor
    # at every nonzero (nncp).  The inline CP sweep reads <X, X_hat>
    # from its last mode's MTTKRP instead.
    sparse_fit: Callable
    weighted_fit: Callable    # (factors, weights, fit_data4) -> fit
    hadamard: Callable        # (grams, exclude=None) -> (R, R)


# ---------------------------------------------------------------------------
# Closure-free sweep function (shared by the sequential, batched and
# distributed engines)
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
    segment path ("psum" | "gather"), as
    ``core.distributed.resolve_collectives`` builds it; see
    ``_build_mttkrp``.
    """
    if fallback not in ("cond", "none"):
        raise ValueError(f"unknown fallback {fallback!r}")
    one_mttkrp = _build_mttkrp(backend, shapes, pallas_meta, interpret,
                               axis, collectives)
    solve = _build_solver(rank, solver, fallback)

    if method != "cp":
        spec = _method_spec(method)
        ctx = SweepContext(
            backend=backend, nmodes=nmodes, rank=rank, shapes=shapes,
            solver=solver, fallback=fallback, axis=axis,
            one_mttkrp=one_mttkrp,
            solve=solve, normalize=normalize_columns,
            sparse_fit=_build_sparse_fit(rank, axis),
            weighted_fit=_build_weighted_fit(axis),
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


def _scan_sweeps(sweep, block: int):
    """``run(state, mode_data_all, fit_data) -> (state, fits (block,))``:
    a ``lax.scan`` of ``block`` consecutive sweeps, one check window."""

    def run_block(state, mode_data_all, fit_data):
        def body(st, _):
            return sweep(st, mode_data_all, fit_data)

        return lax.scan(body, state, xs=None, length=block)

    return run_block


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
    fn = jax.jit(_scan_sweeps(sweep, block),
                 donate_argnums=(0,) if donate else ())
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
    return _initial_state(None, tensor_shape, rank, seed)


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


def _initial_state(spec, tensor_shape, rank: int, seed: int,
                   init_state: tuple | None = None):
    """A fit's starting state on the device: ``init_state`` (a host state
    tuple) when given, else the method's seeded init, else the shared
    one."""
    if init_state is None:
        init = (spec.init_state_host if spec is not None
                and spec.init_state_host is not None else init_state_host)
        init_state = init(tensor_shape, rank, seed)
    factors, grams, weights = init_state
    return (tuple(jnp.asarray(F) for F in factors),
            tuple(jnp.asarray(G) for G in grams), jnp.asarray(weights))


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
    """What a fit uploads and builds before its first dispatch: each
    window runs ``sweep_k(state, *args)``, the shorter last one
    ``sweep_rem``."""

    state: tuple
    args: tuple
    sweep_k: Callable | None
    sweep_rem: Callable | None
    h2d_bytes: int
    hbm_gather_bytes: int = 0       # per sweep (Pallas backend; else 0)


def _prepare_fit(tensor, rank, spec, *, plan, kappa, n_iters, seed,
                 backend, check_every, interpret, donate, solver, method,
                 init_state, weights) -> _PreparedFit:
    N = tensor.nmodes
    state = _initial_state(spec, tensor.shape, rank, seed, init_state)
    if plan is None:
        # The coo backend reads no mode-specific layout: a plan without
        # layouts skips the host-side per-mode sorts.
        plan = (MTTKRPPlan(tensor, kappa, layouts=[]) if backend == "coo"
                else make_plan(tensor, kappa))
    mode_data_all, pallas_meta = collect_mode_data(
        plan, backend, rank,
        valued=spec is not None and spec.valued_mode_data)
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
    return _PreparedFit(
        state, (mode_data_all, fit_data), sweep_k, sweep_rem,
        uploaded_nbytes((state, mode_data_all, fit_data),
                        plan.device_cache()), gather)


def _read_back(fits_dev, state):
    """The fit history, factors and weights on the host: the run's final
    materialization."""
    fits = [float(f) for blk in jax.device_get(fits_dev) for f in blk]
    return (fits, [np.asarray(F) for F in state[0]],
            np.asarray(state[2], dtype=np.float64))


def _run_fit(prepare: Callable[[], _PreparedFit], *, n_iters: int,
             check_every: int, tol: float, verbose: bool, method: str,
             engine: str, kind: str, window_attrs: dict,
             t_start: float) -> CPDResult:
    """The host window loop of ``cpd_als_fused`` and
    ``core.distributed.cpd_als_distributed``: run ``prepare`` under
    ``als.prepare``, dispatch one ``check_every``-sweep window at a time
    under ``als.window`` (with ``window_attrs``), fetch the fit between
    windows only where the run can stop early, read back once, and count
    the fit's dispatches, uploads and preparation time under the ledger
    kind of its executable (``kind``)."""
    tr = obs_trace.active()
    if tr is None:
        prep = prepare()
    else:
        with tr.span("als.prepare", cat="als") as sp:
            prep = prepare()
            sp.set(h2d_bytes=prep.h2d_bytes)
    prepare_s = obs_clock.now() - t_start
    state, args = prep.state, prep.args

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
            state, fits_blk = fn(state, *args)
            if fetch:
                f = float(fits_blk[-1])         # the only in-loop host sync
        else:
            with tr.span("als.window", cat="als", window=b, sweeps=k,
                         **window_attrs):
                with tr.span("als.dispatch", cat="als"):
                    state, fits_blk = fn(state, *args)
                if fetch:
                    with tr.span("als.fetch", cat="als"):
                        f = float(fits_blk[-1])  # the only in-loop sync
        fits_dev.append(fits_blk)
        it += k
        if not fetch:
            continue
        host_syncs += 1
        if verbose:
            print(f"  ALS iter {it:3d}: fit={f:.6f} ({method}/{engine})")
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
    _LEDGER.count(kind, dispatches=len(fits_dev),
                  h2d_bytes=prep.h2d_bytes, prepare_s=prepare_s)
    if prep.hbm_gather_bytes:
        _LEDGER.count(kind, hbm_gather_bytes=it * prep.hbm_gather_bytes)

    return CPDResult(
        factors=factors,
        weights=lam,
        fits=fits,
        iters=it,
        mttkrp_seconds=0.0,
        total_seconds=obs_clock.now() - t_start,
        host_syncs=host_syncs,
        engine=engine,
        method=method,
    )


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
    method shares this front door, the window scan, and the executable
    cache.
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
    check_every = max(1, int(check_every))
    spec = _method_spec(method)
    weights = _checked_entry_weights(spec, method, tensor.nnz, weights)
    if donate is None:
        # Buffer donation is a no-op (with a warning) on CPU.
        donate = jax.default_backend() != "cpu"
    prepare = functools.partial(
        _prepare_fit, tensor, rank, spec, plan=plan, kappa=kappa,
        n_iters=n_iters, seed=seed, backend=backend,
        check_every=check_every, interpret=resolve_interpret(interpret),
        donate=bool(donate), solver=resolve_solver(solver), method=method,
        init_state=init_state, weights=weights)
    return _run_fit(prepare, n_iters=n_iters, check_every=check_every,
                    tol=tol, verbose=verbose, method=method, engine="fused",
                    kind="sweep_block",
                    window_attrs=dict(backend=backend, method=method),
                    t_start=t_start)
