"""Distributed spMTTKRP + fused CPD-ALS via shard_map — κ partitions ↦ κ devices.

The paper maps κ tensor partitions onto κ GPU SMs.  Here κ is the device
count of a 1-D mesh axis (named "sm" in homage).  Per-device shards come
from the single planning layer (``core.plan.build_device_shards``): each
device holds a rectangular, zero-padded slice of the mode layout with
GLOBAL relabeled rows, computes a partial (I_d, R) MTTKRP, and a single
``psum`` combines the partials:

  Scheme 1 (I_d ≥ κ): partials have disjoint row support, so the psum is
    mathematically a concatenation — but it still transfers the full
    (I_d, R) array per device.  A row-sharded output path that skips the
    collective entirely (the paper's "local atomics only" property, which
    the pre-plan host loop kept) is a recorded ROADMAP follow-up; the
    unified psum buys one executable for both schemes and the fused
    window in exchange.
  Scheme 2 (I_d < κ): partials overlap and the psum genuinely reduces —
    the analogue of global atomics, chosen exactly when I_d < κ so the
    payload is tiny.

``cpd_als_distributed`` is the fused engine's distributed twin: it runs
``core.als_device.build_sweep_fn(axis="sm")`` — the SAME closure-free
sweep the sequential and batched engines execute, with psums at the two
shard-crossing points — under ``shard_map``, scanning a whole
``check_every`` window as ONE dispatch.  The host syncs only at window
boundaries (the fit scalar), never inside a window: zero per-iteration
host traffic, matching the single-device fused engine's contract.

Decomposition methods ride the same path (``method=``): value-baked
sweeps (cp, nncp) reuse the standard 4-array mode shards unchanged,
while valued/weighted methods (masked completion) get shards that also
carry full coordinates, values, and per-entry observation weights
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
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import clock as obs_clock
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from . import plan as plan_mod
from .als_device import (_host_state_to_device, _method_spec,
                         build_sweep_fn, normalize_entry_weights,
                         resolve_solver, validate_entry_weights)
from .als_device import init_state as _device_init_state
from .coo import SparseTensor
from .cpd import CPDResult
from .layout import build_mode_layout
from .load_balance import Scheme

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
    if weights is not None:
        if not weighted:
            raise ValueError(
                f"per-entry weights require a weighted-fit method "
                f"(e.g. 'masked'), got method={method!r}")
        weights = normalize_entry_weights(
            validate_entry_weights(tensor.nnz, weights))
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
# One-shot distributed MTTKRP (kept for benchmarks / the kernel oracle)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_rows", "mesh_"))
def _dist_mttkrp(idx, rows, vals, factors, num_rows, mesh_):
    """shard_map body dispatcher (jitted once per shape)."""
    from ..kernels import ref as kref

    def body(idx_s, rows_s, vals_s, *facs):
        out = kref.mttkrp_sorted_segments(
            idx_s[0], rows_s[0], vals_s[0], list(facs), num_rows
        )
        return lax.psum(out, AXIS)

    in_specs = (P(AXIS), P(AXIS), P(AXIS)) + tuple(P() for _ in factors)
    fn = shard_map(body, mesh=mesh_, in_specs=in_specs, out_specs=P())
    return fn(idx, rows, vals, *factors)


def mttkrp_distributed(
    plan: DistributedPlan,
    factors,
    mode: int,
) -> jnp.ndarray:
    """Distributed MTTKRP along ``mode``; returns (I_d, R) f32, original rows."""
    m = plan.modes[mode]
    facs = tuple(jnp.asarray(factors[w]) for w in m.input_modes)
    out = _dist_mttkrp(
        jnp.asarray(m.idx),
        jnp.asarray(m.rows),
        jnp.asarray(m.vals),
        facs,
        num_rows=m.num_rows,
        mesh_=plan.mesh,
    )
    # relabeled -> original rows (replicated output, replicated gather).
    return jnp.zeros_like(out).at[jnp.asarray(m.row_perm[0])].set(out)


# ---------------------------------------------------------------------------
# Fused distributed ALS (shard_map of the one-dispatch-per-window sweep)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_dist_sweep_block(mesh_: Mesh, nmodes: int, rank: int,
                            shapes: tuple[int, ...], solver: str,
                            block: int, method: str = "cp",
                            mode_widths: tuple[int, ...] = None,
                            fit_width: int = 3,
                            collectives: tuple[str, ...] | None = None):
    """Jitted shard_map of ``block`` consecutive distributed sweeps.

    The body squeezes each device's leading shard dim and scans the SAME
    sweep the fused engine uses (``build_sweep_fn`` with ``axis=AXIS``):
    the whole check window is one dispatch, partial MTTKRPs combine
    inside it, and state stays replicated (identical on every device
    because the collective outputs are identical).  Cached per (mesh,
    shapes, rank, solver, window, method, collectives) — shard caps live
    in the array shapes, so same-class tensors reuse the executable.

    ``mode_widths`` / ``fit_width``: how many sharded arrays each mode /
    the fit contract contributes — 4/3 per mode for value-baked psum
    sweeps (cp, nncp), 6 for a gather-collective mode (owned-row slice
    and destination map ride along), 6/4 for the valued+weighted masked
    contract (full coordinates and entry weights).
    ``collectives``: per-mode "psum"/"gather" choice forwarded to
    ``build_sweep_fn`` (gather = the scheme-1 payload fix)."""
    if mode_widths is None:
        mode_widths = (4,) * nmodes
    sweep = build_sweep_fn("segment", nmodes, rank, shapes, None, None,
                           solver, axis=AXIS, method=method,
                           collectives=collectives)
    offs = [0]
    for w in mode_widths:
        offs.append(offs[-1] + w)

    def body(state, *flat):
        md = tuple(
            tuple(jnp.squeeze(a, 0) for a in flat[offs[d]: offs[d + 1]])
            for d in range(nmodes)
        )
        fd = tuple(jnp.squeeze(a, 0) for a in flat[offs[-1]:])

        def step(st, _):
            return sweep(st, md, fd)

        state, fits = lax.scan(step, state, xs=None, length=block)
        return state, fits

    n_sharded = offs[-1] + fit_width
    fn = shard_map(
        body, mesh=mesh_,
        in_specs=(P(),) + tuple(P(AXIS) for _ in range(n_sharded)),
        out_specs=(P(), P()),
    )
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
    """Flat per-mode + fit device arrays in the order the sweep expects:
    ``(idx, rows, vals, row_perm)`` per mode for value-baked psum sweeps
    (``+ (own_rows, gather_map)`` for gather-collective modes),
    ``(idx, rows, row_perm, idx_full, vals, ew)`` for the valued/weighted
    masked contract (see ``methods.masked``).  Also returns the per-mode
    widths for the flat-arg slicing.  Each array is placed straight onto
    the mesh, one leading-axis slice per device, so no device ever holds
    another device's shard."""
    shard = NamedSharding(plan.mesh, P(AXIS))
    flat = []
    widths = []
    for d, m in enumerate(plan.modes):
        if m.idx_full is not None:
            arrs = [m.idx, m.rows, m.row_perm, m.idx_full, m.vals, m.ew]
        elif collectives is not None and collectives[d] == "gather":
            arrs = [m.idx, m.rows, m.vals, m.row_perm, m.own_rows,
                    m.gather_map]
        else:
            arrs = [m.idx, m.rows, m.vals, m.row_perm]
        flat += arrs
        widths.append(len(arrs))
    flat += list(plan.fit_shards)
    return [jax.device_put(a, shard) for a in flat], tuple(widths)


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
    shard_map.  Same init and update order as single-device ``cpd_als``
    (identical seed ⇒ matching factors to fp32 tolerance); the host
    fetches only the window-boundary fit scalar — zero per-iteration
    syncs inside a check window.

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
    N = tensor.nmodes
    shapes = tuple(int(s) for s in tensor.shape)
    check_every = max(1, int(check_every))
    solver = resolve_solver(solver)

    if init_state is not None:
        state = _host_state_to_device(init_state)
    elif spec is not None and spec.init_state_host is not None:
        state = _host_state_to_device(
            spec.init_state_host(tensor.shape, rank, seed))
    else:
        # (init_state the *parameter* shadows the module-level helper.)
        state = _device_init_state(tensor.shape, rank, seed)
    collectives = resolve_collectives(plan, collective)
    flat, mode_widths = _collect_dist_data(plan, collectives)
    fit_width = len(plan.fit_shards)

    n_blocks, rem = divmod(n_iters, check_every)
    fn_k = _build_dist_sweep_block(plan.mesh, N, rank, shapes, solver,
                                   check_every, method, mode_widths,
                                   fit_width, collectives
                                   ) if n_blocks else None
    fn_rem = _build_dist_sweep_block(plan.mesh, N, rank, shapes, solver,
                                     rem, method, mode_widths,
                                     fit_width, collectives
                                     ) if rem else None

    κ = plan.kappa
    shard_nnz = [int(m.nnz_per_dev) for m in plan.modes]
    fits_dev: list = []
    host_syncs = 0
    last_fit = -np.inf
    it = 0
    tr = obs_trace.active()
    for b in range(n_blocks + (1 if rem else 0)):
        k = check_every if b < n_blocks else rem
        fn = fn_k if b < n_blocks else fn_rem
        # Per-window shard_map dispatch; the span carries the mesh size
        # and the per-mode padded shard nnz so a trace attributes window
        # time to shard load.  Disabled branch: one global read, zero
        # allocations.
        if tr is None:
            state, fits_blk = fn(state, *flat)
            f = float(fits_blk[-1])             # the only in-loop host sync
        else:
            with tr.span("dist.window", cat="dist", method=method,
                         kappa=κ, window=b, sweeps=k,
                         shard_nnz=shard_nnz):
                state, fits_blk = fn(state, *flat)
                f = float(fits_blk[-1])         # the only in-loop host sync
        fits_dev.append(fits_blk)
        it += k
        host_syncs += 1
        if verbose:
            print(f"  ALS iter {it:3d}: fit={f:.6f} (distributed)")
        if abs(f - last_fit) < tol:
            break
        last_fit = f

    host_syncs += 1                             # final materialization
    fits = [float(f) for blk in jax.device_get(fits_dev) for f in blk]
    return CPDResult(
        factors=[np.asarray(F) for F in state[0]],
        weights=np.asarray(state[2], dtype=np.float64),
        fits=fits,
        iters=it,
        mttkrp_seconds=0.0,
        total_seconds=obs_clock.now() - t_start,
        host_syncs=host_syncs,
        engine="distributed",
        method=method,
    )
