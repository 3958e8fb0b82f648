"""Distributed spMTTKRP + fused CPD-ALS via shard_map — κ partitions ↦ κ devices.

The paper maps κ tensor partitions onto κ GPU SMs.  Here κ is the device
count of a 1-D mesh axis (named "sm" in homage).  Per-device shards come
from the single planning layer (``core.plan.build_device_shards``): each
device holds a rectangular, zero-padded slice of the mode layout with
GLOBAL relabeled rows and computes a partial (I_d, R) MTTKRP with
``core.mttkrp.mttkrp_mode``, the one MTTKRP dispatch.  The cross-device
combine sits between the kernel and the scatter to original rows:

  Scheme 1 (I_d ≥ κ): partials have disjoint row support.  The default
    ``psum`` still transfers the full (I_d, R) array per device;
    ``collective="gather"`` all-gathers only each device's owned rows
    (``owned_rows_combine``).
  Scheme 2 (I_d < κ): partials overlap and the psum genuinely reduces —
    the analogue of global atomics, chosen exactly when I_d < κ so the
    payload is tiny.

``cpd_als_distributed`` is the fused engine's distributed twin: it runs
``core.als_device.build_sweep_fn(axis="sm")`` — the SAME closure-free
sweep the sequential and batched engines execute — under ``shard_map``,
one dispatch per ``check_every`` window, and drives those windows with
the fused engine's host window loop (``core.als_device._run_fit``):
the same state initialisation, ``als.*`` spans, per-fit ledger counts
(kind ``dist_block``), and the rule that a run with ``tol <= 0``
fetches no fit until its one read-back.

Decomposition methods ride the same path (``method=``): value-baked
sweeps (cp, nncp) use the standard mode shards, while valued/weighted
methods (masked completion) get shards that also carry full
coordinates, values, and per-entry observation weights
(``core.plan.DeviceShards.idx_full`` / ``.ew``) — each device evaluates
the per-sweep residual at its own shard's coordinates from the
replicated factors, the partial residual MTTKRPs psum, the closed-form
dense correction is replicated-exact (no collective), and the weighted
fit psums per-shard residual mass.  ``weights=`` threads user-supplied
fractional observation confidences through the shards, matching the
sequential and batched front doors to fp32 tolerance (pinned by
``tests/conformance``).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import clock as obs_clock
from ..obs.ledger import LEDGER as _LEDGER
from . import plan as plan_mod
from .als_device import (_checked_entry_weights, _initial_state,
                         _method_spec, _PreparedFit, _run_fit, _scan_sweeps,
                         build_sweep_fn, resolve_solver, uploaded_nbytes)
from .coo import SparseTensor
from .cpd import CPDResult
from .layout import build_mode_layout
from .load_balance import Scheme
from .mttkrp import mttkrp_mode, psum_combine

AXIS = "sm"


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axis check off.  The ALS sweeps
    combine per-device partials with explicit ``psum``/``all_gather`` and
    carry replicated state through ``lax.scan``/``lax.while_loop``; with
    the check on, such a carry comes back typed as device-varying (it
    mixes in per-device shard arrays) and the loop refuses the mismatch.
    The distributed sweeps here and the pod engine
    (``serve.batched_engine``) go through this one wrapper."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _local(shards):
    """Inside ``shard_map``: drop each shard's leading device axis."""
    return jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0), shards)


@dataclasses.dataclass
class DistributedPlan:
    """All-modes distributed plan over a 1-D device mesh: one
    ``core.plan.DeviceShards`` per mode plus sharded fit data.

    ``method`` is part of the plan identity: valued/weighted methods
    (masked) shard different arrays (full coordinates + entry weights),
    so a plan built for one method cannot silently serve another."""

    tensor: SparseTensor
    mesh: Mesh
    modes: list[plan_mod.DeviceShards]
    fit_shards: tuple  # (idx (κ,per,N), vals (κ,per)[, ew], norm_sq (κ,))
    method: str = "cp"

    @property
    def kappa(self) -> int:
        return self.mesh.devices.size


def make_distributed_plan(
    tensor: SparseTensor,
    mesh: Mesh | None = None,
    *,
    scheme: Scheme | None = None,
    assignment: str = "greedy",
    method: str = "cp",
    weights: np.ndarray | None = None,
) -> DistributedPlan:
    """Build per-device shards for ``method``.  Value-baked methods get
    the standard structural shards; valued/weighted ones (masked) get
    shards carrying full coordinates and per-entry observation weights
    (``weights=`` — canonical COO order, defaulting to all-ones; padding
    slots are weight 0, the exact-no-op mechanism)."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    spec = _method_spec(method)
    structural = spec is not None and spec.valued_mode_data
    weighted = spec is not None and spec.weighted_fit
    weights = _checked_entry_weights(spec, method, tensor.nnz, weights)
    ew_full = None
    if weighted:
        ew_full = (np.ones(tensor.nnz, np.float32) if weights is None
                   else weights)
    κ = int(mesh.devices.size)
    modes = []
    for d in range(tensor.nmodes):
        lay = build_mode_layout(tensor, d, κ, scheme=scheme,
                                assignment=assignment)
        modes.append(plan_mod.build_device_shards(
            lay,
            weights=ew_full if structural else None,
            with_full_indices=structural,
        ))
    fit = plan_mod.shard_fit_data(tensor, κ, weights=ew_full)
    return DistributedPlan(tensor=tensor, mesh=mesh, modes=modes,
                           fit_shards=fit, method=method)


# ---------------------------------------------------------------------------
# One-shot distributed MTTKRP
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode", "num_rows", "mesh_"))
def _mttkrp_sharded(shards, factors, mode, num_rows, mesh_):
    """shard_map of ``core.mttkrp.mttkrp_mode`` over the value-baked
    segment shards (jitted once per shape)."""

    def body(shards_, factors_):
        return mttkrp_mode("segment", mode, _local(shards_), factors_,
                           num_rows, combine=psum_combine(AXIS))

    fn = shard_map(body, mesh=mesh_, in_specs=(P(AXIS), P()), out_specs=P())
    return fn(shards, factors)


def mttkrp_distributed(
    plan: DistributedPlan,
    factors,
    mode: int,
) -> jnp.ndarray:
    """Distributed MTTKRP along ``mode``; returns (I_d, R) f32, original rows."""
    m = plan.modes[mode]
    shards = tuple(jnp.asarray(a) for a in (m.idx, m.rows, m.vals,
                                            m.row_perm))
    return _mttkrp_sharded(shards, tuple(jnp.asarray(f) for f in factors),
                           mode=mode, num_rows=m.num_rows, mesh_=plan.mesh)


# ---------------------------------------------------------------------------
# Fused distributed ALS (shard_map of the one-dispatch-per-window sweep)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_dist_sweep_block(mesh_: Mesh, nmodes: int, rank: int,
                            shapes: tuple[int, ...], solver: str,
                            block: int, method: str = "cp",
                            collectives: tuple[str, ...] | None = None):
    """Jitted shard_map of ``block`` consecutive distributed sweeps:
    ``run(state, mode_data_all, fit_data)`` with every mode- and fit-data
    array sharded on its leading device axis.

    The body scans the SAME sweep the fused engine uses
    (``build_sweep_fn`` with ``axis=AXIS``): the whole check window is
    one dispatch, partial MTTKRPs combine inside it, and state stays
    replicated (identical on every device because the collective outputs
    are identical).  Cached per (mesh, shapes, rank, solver, window,
    method, collectives) — shard caps live in the array shapes, so
    same-class tensors reuse the executable.  ``collectives``: per-mode
    "psum"/"gather" choice forwarded to ``build_sweep_fn``."""
    sweep = build_sweep_fn("segment", nmodes, rank, shapes, None, None,
                           solver, axis=AXIS, method=method,
                           collectives=collectives)
    run_block = _scan_sweeps(sweep, block)

    def body(state, mode_data_all, fit_data):
        return run_block(state, *_local((mode_data_all, fit_data)))

    fn = shard_map(body, mesh=mesh_, in_specs=(P(), P(AXIS), P(AXIS)),
                   out_specs=(P(), P()))
    return _LEDGER.register(
        "dist_block",
        (nmodes, rank, shapes, "kappa", int(mesh_.devices.size),
         "block", block, "method", method, "collectives", collectives),
        jax.jit(fn))


def resolve_collectives(plan: DistributedPlan,
                        collective: str) -> tuple[str, ...] | None:
    """Per-mode collective tuple for ``collective`` ("psum" | "gather").

    "gather" applies per mode only where the shards support it (scheme 1,
    value-baked): scheme-2 modes keep the psum (their partials genuinely
    overlap), so a mixed-scheme tensor still benefits on the modes that
    can.  Returns None for the pure-psum configuration so the executable
    cache key (and hence every pre-existing cache entry) is unchanged."""
    if collective == "psum":
        return None
    if collective != "gather":
        raise ValueError(f"unknown collective {collective!r}")
    if plan.modes[0].idx_full is not None:
        raise ValueError(
            "collective='gather' supports value-baked methods only "
            "(cp, nncp); the valued/weighted contract psums residual "
            "MTTKRPs")
    out = tuple("gather" if m.own_rows is not None else "psum"
                for m in plan.modes)
    return out


def collective_payload_bytes(plan: DistributedPlan, rank: int,
                             collectives: tuple[str, ...] | None) -> int:
    """Bytes crossing the mesh per sweep to combine the N mode outputs:
    psum moves every device's full (I_d, R) partial; gather moves each
    device's (rows_cap, R) owned slice plus its int32 destination map."""
    κ = plan.kappa
    total = 0
    for d, m in enumerate(plan.modes):
        if collectives is not None and collectives[d] == "gather":
            total += κ * m.rows_cap * (rank * 4 + 4)
        else:
            total += κ * m.num_rows * rank * 4
    return int(total)


def _collect_dist_data(plan: DistributedPlan,
                       collectives: tuple[str, ...] | None = None):
    """``(mode_data_all, fit_data)`` of the distributed sweep: per mode
    ``(idx, rows, vals, row_perm)`` for value-baked psum sweeps
    (``+ (own_rows, gather_map)`` for gather-collective modes),
    ``(idx, rows, row_perm, idx_full, vals, ew)`` for the valued/weighted
    masked contract (see ``methods.masked``).  Each array is placed
    straight onto the mesh, one leading-axis slice per device, so no
    device ever holds another device's shard."""
    mode_data_all = []
    for d, m in enumerate(plan.modes):
        if m.idx_full is not None:
            arrs = (m.idx, m.rows, m.row_perm, m.idx_full, m.vals, m.ew)
        elif collectives is not None and collectives[d] == "gather":
            arrs = (m.idx, m.rows, m.vals, m.row_perm, m.own_rows,
                    m.gather_map)
        else:
            arrs = (m.idx, m.rows, m.vals, m.row_perm)
        mode_data_all.append(arrs)
    return jax.device_put((tuple(mode_data_all), tuple(plan.fit_shards)),
                          NamedSharding(plan.mesh, P(AXIS)))


def cpd_als_distributed(
    tensor: SparseTensor,
    rank: int,
    mesh: Mesh | None = None,
    *,
    plan: DistributedPlan | None = None,
    n_iters: int = 25,
    tol: float = 1e-5,
    seed: int = 0,
    check_every: int = 1,
    solver: str = "auto",
    method: str = "cp",
    weights: np.ndarray | None = None,
    init_state: tuple | None = None,
    collective: str = "psum",
    verbose: bool = False,
) -> CPDResult:
    """Distributed CPD-ALS: the fused one-dispatch-per-window sweep under
    shard_map, run by the fused engine's host window loop.  Same
    init and update order as single-device ``cpd_als`` (identical seed ⇒
    matching factors to fp32 tolerance); the host fetches only the
    window-boundary fit scalar, and none at all when ``tol <= 0``.

    ``method`` selects the decomposition method (sweep-based methods
    only: cp, nncp, masked); ``weights`` threads per-entry observation
    confidences through the shards for weighted-fit methods; and
    ``init_state`` warm-starts from existing factors — the same contracts
    as the sequential and batched front doors, so the three agree to fp32
    tolerance (``tests/conformance``).

    ``collective`` — how per-device partial mode outputs combine:
    "psum" (default; both schemes) or "gather" (scheme-1 modes all-gather
    just their owned row slices, ~1/kappa of the psum payload; scheme-2
    modes silently keep the psum).  Both produce identical factors up to
    fp32 summation order."""
    t_start = obs_clock.now()
    spec = _method_spec(method)
    if plan is None:
        plan = make_distributed_plan(tensor, mesh, method=method,
                                     weights=weights)
    elif plan.method != method:
        raise ValueError(
            f"distributed plan was built for method {plan.method!r}, "
            f"got method={method!r}; rebuild with make_distributed_plan")
    elif weights is not None:
        raise ValueError(
            "pass weights to make_distributed_plan (they are sharded into "
            "the plan); a prebuilt plan already carries its weights")
    check_every = max(1, int(check_every))

    def prepare() -> _PreparedFit:
        state = _initial_state(spec, tensor.shape, rank, seed, init_state)
        collectives = resolve_collectives(plan, collective)
        data = _collect_dist_data(plan, collectives)
        block = functools.partial(
            _build_dist_sweep_block, plan.mesh, tensor.nmodes, rank,
            tuple(int(s) for s in tensor.shape), resolve_solver(solver),
            method=method, collectives=collectives)
        n_blocks, rem = divmod(n_iters, check_every)
        return _PreparedFit(state, data,
                            block(check_every) if n_blocks else None,
                            block(rem) if rem else None,
                            uploaded_nbytes((state, data)))

    # The mesh size and each mode's padded shard nnz ride on every
    # window's span, so a trace attributes window time to shard load.
    return _run_fit(prepare, n_iters=n_iters, check_every=check_every,
                    tol=tol, verbose=verbose, method=method,
                    engine="distributed", kind="dist_block",
                    window_attrs=dict(
                        backend="segment", method=method, kappa=plan.kappa,
                        shard_nnz=[int(m.nnz_per_dev) for m in plan.modes]),
                    t_start=t_start)
