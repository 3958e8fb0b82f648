"""Vmapped batched ALS engine: B same-bucket decompositions, one dispatch.

The small-tensor regime is overhead-dominated — a single sweep cannot
saturate the device — so the serving path stacks B bucket-mates (same
shape, nnz padded to the bucket cap, see ``serve.buckets``) and runs
``jax.vmap`` of the *same* closure-free sweep the sequential engine jits
(``core.als_device.build_sweep_fn``).  One dispatch then advances B
decompositions by a whole ``check_every`` window (``lax.scan``, exactly
mirroring the sequential engine's window structure):

  * per-tensor convergence masking: every tensor keeps sweeping until the
    whole batch is done, but a converged (or iteration-capped) tensor's
    state is frozen under ``jnp.where`` — its factors, fit, and iteration
    counter stop changing, so batching never alters an individual
    result.  Convergence is judged on device at window boundaries
    against the previous boundary's fit — the sequential engine's exact
    stopping rule, vectorized — so a request converges at the same
    iteration whichever front door served it (for a uniform-``n_iters``
    batch; mixed budgets can shift a straggler's window grid).
  * the batch state pytree is donated (off-CPU), so XLA reuses the B-way
    buffers in place across windows.
  * executables are cached per (bucket shape, nnz cap, B, rank, backend,
    solver, window, METHOD): a warm bucket class pays zero retrace per
    batch.  ``batched_cache_stats()`` exposes the counters.

Decomposition methods (``repro.methods``) batch through the same door:
``decompose_batch(method=...)`` vmaps that method's sweep under the same
executable cache.  The masked method's mode data is structural-only
(per-sweep residual values are scattered on device), its fit data
carries per-entry observation weights — user-supplied fractional
confidences via ``weights=`` (default 1), zeroed on nnz padding, which
is what keeps padding exact for completion — and ``init_states`` threads
warm starts (the streaming method's increments) through the service.

Backends: ``segment`` (default; per-tensor mode layouts are stacked —
same padded nnz ⇒ identical array shapes regardless of which
load-balancing scheme each tensor picked), ``coo`` (no host-side layout
preprocessing at all), and ``pallas``: each bucket-mate's layout is
packed to the bucket's static ``core.plan`` slab cap, so the slab arrays
share one shape and the kernel vmaps (interpreted on the CPU backend).  The
pallas path packs the UNPADDED tensors (slab-cap padding replaces nnz
padding), which keeps the batched result bit-identical to the
per-request sequential pallas engine under the same plan (the masked
method packs the PADDED tensors instead — its weight-0 entries are
already exact no-ops and the residual scatter needs one consistent
canonical order).

``density`` (an observed per-bucket row-density profile from
``serve.metrics``) reprices the bucket plan's tilings against the
stream's real skew instead of the uniform prior — see
``core.plan.plan_bucket``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import als_device
from ..core import plan as plan_mod
from ..core.coo import SparseTensor
from ..core.cpd import CPDResult
from ..core.distributed import shard_map
from ..core.layout import build_all_mode_layouts
from ..kernels import ops as kops
from ..kernels.mttkrp_pallas import resolve_interpret
from ..obs import clock as obs_clock
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from .buckets import pad_tensor, pad_weights, repeat_pad

_BATCH_BACKENDS = ("segment", "coo", "pallas")


def _all_finite(tree) -> jnp.ndarray:
    """Scalar bool: every float leaf of ``tree`` is finite."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)]
    ok = jnp.bool_(True)
    for l in leaves:
        ok = ok & jnp.all(jnp.isfinite(l))
    return ok


def _make_window_runner(backend: str, nmodes: int, rank: int,
                        shapes: tuple[int, ...], interpret: bool,
                        solver: str, block: int,
                        pallas_meta: tuple | None, method: str):
    """The pure one-check-window function shared by the single-device
    batched block and the pod block: ``run_block(carry, mode_data_all,
    fit_data, tol_b, max_iters_b) -> (carry, fits (block, B))`` — a
    ``lax.scan`` of ``block`` vmapped sweeps with per-tensor convergence
    masking and the batch-level pinv-fallback cond.

    The pinv fallback is HOISTED to a batch-level ``lax.cond``: the
    window first scans a fallback-free sweep (under vmap a per-element
    ``lax.cond`` lowers to a select that always pays the small-R SVD);
    only if any float in the result is non-finite does the window re-run
    with the guarded sweep.  Well-conditioned batches — the overwhelming
    majority — never touch the SVD.  (For a method without a solve —
    HALS — the two sweeps coincide and the cond is a cheap no-op.)

    carry = (state, active (B,) bool, last_fit (B,), done (B,) int32)."""
    sweep_fast = als_device.build_sweep_fn(backend, nmodes, rank, shapes,
                                           pallas_meta, interpret, solver,
                                           fallback="none", method=method)
    sweep_safe = als_device.build_sweep_fn(backend, nmodes, rank, shapes,
                                           pallas_meta, interpret, solver,
                                           fallback="cond", method=method)
    vfast = jax.vmap(sweep_fast, in_axes=(0, 0, 0))
    vsafe = jax.vmap(sweep_safe, in_axes=(0, 0, 0))

    def run_block(carry, mode_data_all, fit_data, tol_b, max_iters_b):
        fit_ref = carry[2]       # fit at the previous window boundary

        def make_body(vsweep):
            def body(c, _):
                state, active, last_fit, done = c
                new_state, fit = vsweep(state, mode_data_all, fit_data)

                def freeze(new, old):
                    mask = active.reshape(
                        (active.shape[0],) + (1,) * (new.ndim - 1))
                    return jnp.where(mask, new, old)

                state = jax.tree_util.tree_map(freeze, new_state, state)
                fit = jnp.where(active, fit, last_fit)
                done = done + active.astype(jnp.int32)
                active = active & (done < max_iters_b)
                return (state, active, fit, done), fit
            return body

        fast_carry, fast_fits = lax.scan(make_body(vfast), carry, xs=None,
                                         length=block)
        # Batch-level all-finite gate: carry[2] (the -inf initial boundary
        # fit) is deliberately excluded — only the NEW state and fits
        # decide whether the guarded re-run is needed.
        ok = _all_finite((fast_carry[0], fast_fits))

        def rerun_safe(_):
            return lax.scan(make_body(vsafe), carry, xs=None, length=block)

        (state, active, fit, done), fits = lax.cond(
            ok, lambda _: (fast_carry, fast_fits), rerun_safe, None)
        # Convergence is judged at the WINDOW boundary against the previous
        # boundary's fit — the same rule (and therefore the same stopping
        # iteration) as the sequential fused engine, just vectorized.
        active = active & ~(jnp.abs(fit - fit_ref) < tol_b)
        return (state, active, fit, done), fits

    return run_block


@functools.lru_cache(maxsize=None)
def _build_batched_block(backend: str, nmodes: int, rank: int,
                         shapes: tuple[int, ...], nnz_cap: int, batch: int,
                         interpret: bool, donate: bool, solver: str,
                         block: int, pallas_meta: tuple | None = None,
                         method: str = "cp"):
    """Jitted one-check-window batched block (see ``_make_window_runner``).
    ``nnz_cap`` and ``batch`` are part of the key so the cache honestly
    counts one executable per (bucket, B) class."""
    run_block = _make_window_runner(backend, nmodes, rank, shapes,
                                    interpret, solver, block, pallas_meta,
                                    method)
    return _LEDGER.register(
        "batched_block",
        (backend, nmodes, rank, shapes, "cap", nnz_cap, "B", batch,
         "block", block, "method", method),
        jax.jit(run_block, donate_argnums=(0,) if donate else ()))


@functools.lru_cache(maxsize=None)
def _build_pod_block(mesh_, backend: str, nmodes: int, rank: int,
                     shapes: tuple[int, ...], nnz_cap: int,
                     batch_per_dev: int, interpret: bool, donate: bool,
                     solver: str, block: int, max_windows: int,
                     pallas_meta: tuple | None = None, method: str = "cp"):
    """The pod executable: ``shard_map`` over the mesh's batch axis of a
    ``lax.while_loop`` over whole check windows — a multi-window
    decomposition of B = devices * ``batch_per_dev`` requests is ONE
    device dispatch.

    Each device runs the SAME vmapped window the single-device batched
    block scans (``_make_window_runner``) on its ``batch_per_dev`` lanes;
    the loop condition reads an all-converged flag ``psum``-ed across the
    mesh INSIDE the body (collectives are illegal in a while cond, so the
    flag rides in the loop state) — no host judging between windows.  The
    per-lane ``done < max_iters_b`` freeze caps every lane at exactly its
    own budget, so running full windows only (``max_windows`` =
    ceil(max_iters / block)) produces trajectories identical to the
    single-device engine's remainder-window loop: frozen sweeps are
    no-ops and each lane's fit history is sliced to its own ``done``.

    Returns ``fn(carry, mode_data_all, fit_data, tol_b, max_iters_b) ->
    (carry, fits (max_windows*block, B), windows_run)``."""
    run_block = _make_window_runner(backend, nmodes, rank, shapes,
                                    interpret, solver, block, pallas_meta,
                                    method)
    axis = mesh_.axis_names[0]
    n_dev = int(mesh_.devices.size)
    total_rows = max_windows * block

    def pod_body(carry, mode_data_all, fit_data, tol_b, max_iters_b):
        fits_buf = jnp.zeros((total_rows, carry[1].shape[0]), jnp.float32)

        def wcond(ls):
            _c, _fb, w, global_active = ls
            return (w < max_windows) & global_active

        def wbody(ls):
            c, fb, w, _ = ls
            c, fits_blk = run_block(c, mode_data_all, fit_data, tol_b,
                                    max_iters_b)
            fb = lax.dynamic_update_slice(fb, fits_blk,
                                          (w * block, jnp.int32(0)))
            ga = lax.psum(jnp.any(c[1]).astype(jnp.int32), axis) > 0
            return (c, fb, w + jnp.int32(1), ga)

        carry, fits_buf, w, _ = lax.while_loop(
            wcond, wbody,
            (carry, fits_buf, jnp.int32(0), jnp.bool_(True)))
        return carry, fits_buf, w

    Pb = P(axis)
    fn = shard_map(
        pod_body, mesh=mesh_,
        in_specs=(Pb, Pb, Pb, Pb, Pb),
        out_specs=(Pb, P(None, axis), P()),
    )
    return _LEDGER.register(
        "pod_block",
        (backend, nmodes, rank, shapes, "cap", nnz_cap,
         "B/dev", batch_per_dev, "devices", n_dev, "block", block,
         "windows", max_windows, "method", method),
        jax.jit(fn, donate_argnums=(0,) if donate else ()))


def batched_cache_stats():
    """(hits, misses, currsize) of the batched executable cache, keyed per
    (bucket, B, rank, backend, window, method)."""
    info = _build_batched_block.cache_info()
    return {"hits": info.hits, "misses": info.misses,
            "currsize": info.currsize}


class BatchedEngine:
    """Stacks same-bucket tensors and drives the vmapped fused sweep.

    With ``mesh`` (a 1-D device mesh, e.g. ``launch.mesh.make_batch_mesh``)
    the engine runs the POD path: the batch is padded to a mesh multiple
    (repeat-last-request — exact, lanes are independent), the vmapped
    window is wrapped in ``shard_map`` over the mesh's axis, and the
    whole multi-window decomposition executes as ONE dispatch with
    on-device convergence (``_build_pod_block``).  ``batch_quantum``
    feeds the ``core.plan.PodPlan`` sizing rule so direct engine callers
    and the scheduler agree on dispatched batch sizes."""

    def __init__(self, rank: int, *, kappa: int = 1,
                 backend: str = "segment", check_every: int = 4,
                 interpret: bool | None = None, donate: bool | None = None,
                 solver: str = "auto", mesh=None, batch_quantum: int = 1,
                 lane_placement: str = "balanced"):
        if backend not in _BATCH_BACKENDS:
            raise ValueError(
                f"batched engine supports {_BATCH_BACKENDS}, got "
                f"{backend!r}")
        if lane_placement not in ("balanced", "contiguous"):
            raise ValueError(
                f"lane_placement must be 'balanced' or 'contiguous', got "
                f"{lane_placement!r}")
        self.rank = rank
        self.kappa = kappa
        self.backend = backend
        self.check_every = max(1, int(check_every))
        self.interpret = resolve_interpret(interpret)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        self.solver = als_device.resolve_solver(solver)
        if mesh is not None and len(mesh.axis_names) != 1:
            raise ValueError(
                f"pod mesh must be 1-D (the batch axis), got axes "
                f"{mesh.axis_names}")
        self.mesh = mesh
        self.batch_quantum = max(1, int(batch_quantum))
        self.lane_placement = lane_placement

    @property
    def num_devices(self) -> int:
        """Mesh size of the pod path (1 when running single-device)."""
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    @property
    def pod_plan(self) -> plan_mod.PodPlan:
        """The pod sizing plan, shared by every bucket (mesh path only)."""
        if self.mesh is None:
            raise ValueError("engine has no mesh; pod_plan is undefined")
        return plan_mod.PodPlan(num_devices=self.num_devices,
                                batch_quantum=self.batch_quantum)

    # -- data staging -------------------------------------------------------

    def bucket_plan(self, shape: tuple[int, ...], nnz_cap: int,
                    density: tuple | None = None) -> plan_mod.PartitionPlan:
        """The static plan a (shape, nnz_cap) bucket executes under —
        shared with the sequential path for bit-identical results.
        ``density`` (observed per-mode row-density profile) reprices the
        tilings against the stream's real skew."""
        return plan_mod.plan_bucket(tuple(int(s) for s in shape),
                                    int(nnz_cap), self.rank, self.kappa,
                                    density=density)

    def _stack_pallas(self, source: list[SparseTensor], nnz_cap: int,
                      density, structural: bool):
        """Pack each source tensor to the bucket plan's static slab cap:
        slab-cap padding (appended zero slabs) replaces nnz padding, so
        the packed arrays both stack across bucket-mates AND stay
        bit-identical to the tensor's own sequential packing under the
        same plan.  ``structural=True`` (masked) ships the layout
        permutation + value scatter instead of baked values."""
        N = source[0].nmodes
        bplan = self.bucket_plan(tuple(source[0].shape), nnz_cap, density)
        per_mode: list[list[tuple]] = [[] for _ in range(N)]
        keys: list[tuple | None] = [None] * N
        for t in source:
            for d, lay in enumerate(build_all_mode_layouts(t, self.kappa)):
                mp = bplan.modes[d]
                p = kops.pack_layout(lay, block_rows=mp.block_rows,
                                     tile=mp.tile,
                                     num_slabs_cap=mp.slab_cap)
                # Every bucket-mate must pack to the same static
                # identity or vmap stacking is silently wrong.
                if keys[d] is None:
                    keys[d] = p.bucket_key
                elif p.bucket_key != keys[d]:
                    raise AssertionError(
                        f"plan produced mismatched packings for mode "
                        f"{d}: {p.bucket_key} vs {keys[d]}")
                if structural:
                    per_mode[d].append((p.rb_of, p.first, p.idx_packed,
                                        p.lrows_packed, lay.row_perm,
                                        lay.perm.astype(np.int32),
                                        p.val_scatter))
                else:
                    per_mode[d].append((p.rb_of, p.first, p.idx_packed,
                                        p.vals_packed, p.lrows_packed,
                                        lay.row_perm))
        width = 7 if structural else 6
        mode_data_all = tuple(
            tuple(jnp.asarray(np.stack([rec[j] for rec in per_mode[d]]))
                  for j in range(width))
            for d in range(N)
        )
        return mode_data_all, bplan.pallas_meta()

    def _stack_batch(self, tensors: list[SparseTensor],
                     padded: list[SparseTensor], nnz_cap: int,
                     method: str = "cp", density: tuple | None = None,
                     weights: Sequence | None = None):
        """Stacked per-mode device arrays + fit data for the vmapped sweep.

        Returns ``(mode_data_all, fit_data, pallas_meta)``; the meta tuple
        is ``None`` except for the pallas backend, where it carries the
        bucket plan's static tiling (part of the executable key).
        ``weights`` — optional per-request entry-weight vectors (canonical
        order, ``None`` entries meaning all-ones) for weighted-fit
        methods."""
        spec = None
        if method != "cp":
            from ..methods import get_method

            spec = get_method(method)
        structural = spec is not None and spec.valued_mode_data
        N = padded[0].nmodes
        idx = jnp.asarray(np.stack([t.indices for t in padded]))
        vals = jnp.asarray(np.stack(
            [t.values.astype(np.float32) for t in padded]))
        if spec is not None and spec.weighted_fit:
            # Observation weights: the request's own confidences (default
            # 1) on real entries, 0 on nnz padding — the masked analogue
            # of plain CP's exact zero-value padding, generalized to
            # user-supplied fractional weights.  The norm term weights
            # accordingly so the batched fit matches the sequential one.
            if weights is None:
                weights = [None] * len(tensors)
            ew_rows, norms_w = [], []
            for t, w in zip(tensors, weights):
                base = (np.ones(t.nnz, np.float32) if w is None
                        else als_device.normalize_entry_weights(
                            als_device.validate_entry_weights(t.nnz, w)))
                ew_rows.append(pad_weights(base, nnz_cap))
                v = t.values.astype(np.float32)
                norms_w.append(float((base * v) @ v))
            ew = jnp.asarray(np.stack(ew_rows))
            norms = jnp.asarray(np.array(norms_w, dtype=np.float32))
            fit_data = (idx, vals, ew, norms)
        else:
            norms = jnp.asarray(
                np.array([t.norm() ** 2 for t in padded], dtype=np.float32))
            fit_data = (idx, vals, norms)
        if self.backend == "coo":
            if structural:
                return tuple((idx,) for _ in range(N)), fit_data, None
            coo = (idx, vals)
            return tuple(coo for _ in range(N)), fit_data, None
        if self.backend == "pallas":
            # Masked packs the PADDED tensors (weight-0 entries are exact
            # no-ops and the residual scatter needs the padded canonical
            # order); plain/nncp pack the UNPADDED ones for bit-identity
            # with the sequential path.
            source = padded if structural else tensors
            mode_data_all, meta = self._stack_pallas(
                source, nnz_cap, density, structural)
            return mode_data_all, fit_data, meta
        # segment: build each tensor's mode-specific layouts on host, then
        # stack.  Padding to a common nnz is exactly what makes the layout
        # arrays stack — every bucket-mate yields (nnz_cap, ·) per mode.
        per_mode_s: list[list[tuple]] = [[] for _ in range(N)]
        for t in padded:
            for d, lay in enumerate(build_all_mode_layouts(t, self.kappa)):
                im = lay.input_modes()
                if structural:
                    per_mode_s[d].append((lay.indices[:, im], lay.rows,
                                          lay.row_perm,
                                          lay.perm.astype(np.int32)))
                else:
                    per_mode_s[d].append((lay.indices[:, im], lay.rows,
                                          lay.values.astype(np.float32),
                                          lay.row_perm))
        mode_data_all = tuple(
            tuple(jnp.asarray(np.stack([rec[j] for rec in per_mode_s[d]]))
                  for j in range(4))
            for d in range(N)
        )
        return mode_data_all, fit_data, None

    # -- driver -------------------------------------------------------------

    def prepare_batch(
        self,
        tensors: Sequence[SparseTensor],
        *,
        n_iters: int | Sequence[int] = 25,
        tol: float | Sequence[float] = 1e-5,
        seeds: Sequence[int] | None = None,
        nnz_cap: int | None = None,
        method: str = "cp",
        init_states: Sequence[tuple | None] | None = None,
        density: tuple | None = None,
        weights: Sequence | None = None,
    ) -> "_PreparedBatch | None":
        """HOST half of a batch decomposition: validation, pod padding,
        layout stacking, and init-state assembly — everything up to (but
        not including) the device dispatch.  Pure host work, so the
        scheduler's double-buffered flush path can run it for flush N+1
        while flush N computes on device.  Returns ``None`` for an empty
        batch; feed the result to ``execute_prepared``.  Traced as
        ``serve.prepare``; the ledger counts its uploaded bytes and host
        seconds under the engine's executable kind."""
        t0 = obs_clock.now()
        kw = dict(n_iters=n_iters, tol=tol, seeds=seeds, nnz_cap=nnz_cap,
                  method=method, init_states=init_states, density=density,
                  weights=weights)
        tr = obs_trace.active()
        if tr is None:
            prep = self._prepare_batch(list(tensors), **kw)
        else:
            with tr.span("serve.prepare", cat="serve", backend=self.backend,
                         method=method, requested=len(tensors)) as sp:
                prep = self._prepare_batch(list(tensors), **kw)
                if prep is not None:
                    sp.set(shape=str(prep.shape), nnz_cap=prep.cap,
                           B=prep.batch, h2d_bytes=prep.h2d_bytes)
        if prep is not None:
            _LEDGER.count(self._kind, h2d_bytes=prep.h2d_bytes,
                          prepare_s=obs_clock.now() - t0)
        return prep

    @property
    def _kind(self) -> str:
        """The ledger kind of this engine's executables."""
        return "batched_block" if self.mesh is None else "pod_block"

    def _prepare_batch(self, tensors: list[SparseTensor], *, n_iters, tol,
                       seeds, nnz_cap, method, init_states, density,
                       weights) -> "_PreparedBatch | None":
        if not tensors:
            return None
        spec = None
        if method != "cp":
            from ..methods import get_method

            spec = get_method(method)
            if spec.stateful:
                raise ValueError(
                    f"method {method!r} is stateful; drive it through its "
                    f"session API (ALSRunner.open_stream)")
        if weights is not None and any(w is not None for w in weights) and (
                spec is None or not spec.weighted_fit):
            raise ValueError(
                f"per-entry weights require a weighted-fit method "
                f"(e.g. 'masked'), got method={method!r}")
        t_start = obs_clock.now()
        requested = len(tensors)
        shape = tuple(int(s) for s in tensors[0].shape)
        for t in tensors:
            if tuple(t.shape) != shape:
                raise ValueError(
                    f"batch mixes shapes {shape} and {tuple(t.shape)}; "
                    f"bucket before batching")
        N = len(shape)
        cap = int(nnz_cap) if nnz_cap is not None else max(t.nnz
                                                           for t in tensors)

        if seeds is None:
            seeds = [0] * requested
        if len(seeds) != requested:
            raise ValueError("seeds must match batch size")
        if init_states is not None and len(init_states) != requested:
            raise ValueError("init_states must match batch size")
        if weights is not None and len(weights) != requested:
            raise ValueError("weights must match batch size")
        n_iters_b = np.broadcast_to(
            np.asarray(n_iters, dtype=np.int32), (requested,)).copy()
        tol_b = np.broadcast_to(
            np.asarray(tol, dtype=np.float32), (requested,)).copy()

        if self.mesh is not None:
            # Pod sizing: round the batch up to a mesh multiple (through
            # the batch_quantum first — one shared PodPlan rule) and
            # repeat the last request into the padding lanes.  Exact:
            # lanes are independent under vmap/shard_map and the padded
            # lanes' results are discarded below.
            B, _ = self.pod_plan.dispatch_batch(requested)
            if B > requested:
                tensors = repeat_pad(tensors, B)
                seeds = repeat_pad(list(seeds), B)
                n_iters_b = np.asarray(repeat_pad(list(n_iters_b), B),
                                       dtype=np.int32)
                tol_b = np.asarray(repeat_pad(list(tol_b), B),
                                   dtype=np.float32)
                if init_states is not None:
                    init_states = repeat_pad(list(init_states), B)
                if weights is not None:
                    weights = repeat_pad(list(weights), B)
            # Load-aware lane placement: shard_map splits the stacked
            # batch axis into contiguous per-device blocks, so arrival
            # order decides which device carries the heavy requests.
            # Deal lanes heaviest-first to the least-loaded device;
            # results are un-permuted in _materialize (lanes are
            # independent, so per-request numerics are unchanged).
            lane_of = None
            if self.lane_placement == "balanced":
                order = plan_mod.pod_lane_order(
                    [int(t.nnz) for t in tensors], self.num_devices)
                if order != list(range(B)):
                    tensors = [tensors[i] for i in order]
                    seeds = [seeds[i] for i in order]
                    idx = np.asarray(order)
                    n_iters_b = np.asarray(n_iters_b)[idx]
                    tol_b = np.asarray(tol_b)[idx]
                    if init_states is not None:
                        init_states = [init_states[i] for i in order]
                    if weights is not None:
                        weights = [weights[i] for i in order]
                    lane_of = [0] * B
                    for lane, i in enumerate(order):
                        lane_of[i] = lane
        else:
            B = requested
            lane_of = None

        padded = [pad_tensor(t, cap) for t in tensors]
        mode_data_all, fit_data, pallas_meta = self._stack_batch(
            tensors, padded, cap, method, density, weights)
        # Host-side init, stacked once: one upload per state leaf instead
        # of 2N+1 tiny transfers (and N gram dispatches) per tensor.
        init_fn = (spec.init_state_host if spec is not None
                   and spec.init_state_host is not None
                   else als_device.init_state_host)
        inits = [
            (init_states[i] if init_states is not None
             and init_states[i] is not None
             else init_fn(shape, self.rank, int(seeds[i])))
            for i in range(B)
        ]
        state = (
            tuple(jnp.asarray(np.stack([st[0][d] for st in inits]))
                  for d in range(N)),
            tuple(jnp.asarray(np.stack([st[1][d] for st in inits]))
                  for d in range(N)),
            jnp.asarray(np.stack([st[2] for st in inits])),
        )
        carry = (
            state,
            jnp.ones((B,), dtype=bool),
            jnp.full((B,), -jnp.inf, dtype=jnp.float32),
            jnp.zeros((B,), dtype=jnp.int32),
        )
        tol_dev, max_iters_dev = jnp.asarray(tol_b), jnp.asarray(n_iters_b)
        return _PreparedBatch(
            requested=requested,
            batch=B,
            shape=shape,
            cap=cap,
            method=method,
            carry=carry,
            mode_data_all=mode_data_all,
            fit_data=fit_data,
            tol_dev=tol_dev,
            max_iters_dev=max_iters_dev,
            max_iters=int(n_iters_b.max()),
            pallas_meta=pallas_meta,
            lane_nnz=[int(t.nnz) for t in tensors],
            lane_of=lane_of,
            t_start=t_start,
            h2d_bytes=als_device.uploaded_nbytes(
                (state, mode_data_all, fit_data, tol_dev, max_iters_dev)),
        )

    def execute_prepared(self, prep: "_PreparedBatch | None"
                         ) -> list[CPDResult]:
        """DEVICE half: dispatch a prepared batch and materialize results.
        Single-device engines run the host-judged check-window loop; a
        mesh engine runs the pod block — the entire multi-window run is
        ONE dispatch with the convergence loop on device."""
        if prep is None:
            return []
        if self.mesh is not None:
            return self._execute_pod(prep)
        return self._execute_loop(prep)

    def decompose_batch(
        self,
        tensors: Sequence[SparseTensor],
        *,
        n_iters: int | Sequence[int] = 25,
        tol: float | Sequence[float] = 1e-5,
        seeds: Sequence[int] | None = None,
        nnz_cap: int | None = None,
        method: str = "cp",
        init_states: Sequence[tuple | None] | None = None,
        density: tuple | None = None,
        weights: Sequence | None = None,
    ) -> list[CPDResult]:
        """Decompose B same-shape tensors in vmapped lockstep.

        ``n_iters`` / ``tol`` / ``seeds`` may be scalars or per-tensor
        sequences (requests batched together keep their own budgets).
        ``method`` selects the decomposition method (all B requests share
        it — the scheduler keys buckets on method); ``init_states`` is an
        optional per-tensor list of host state tuples (see
        ``als_device.state_from_factors``) warm-starting individual
        requests — ``None`` entries fall back to the method's seeded init.
        ``weights`` is an optional per-tensor list of entry-weight vectors
        (canonical COO order; ``None`` entries mean all-ones) for
        weighted-fit methods — padding appends weight-0 slots, so a
        weighted batched request matches its sequential run.
        Returned ``CPDResult``s carry per-tensor factors/fits/iters;
        ``total_seconds`` and ``host_syncs`` are *batch-level* (shared by
        all B results — the whole point is that the batch paid them once).

        This is ``execute_prepared(prepare_batch(...))`` — the split
        exists so the scheduler can overlap host assembly with device
        compute (double buffering).
        """
        return self.execute_prepared(self.prepare_batch(
            tensors, n_iters=n_iters, tol=tol, seeds=seeds, nnz_cap=nnz_cap,
            method=method, init_states=init_states, density=density,
            weights=weights))

    def _execute_loop(self, prep: "_PreparedBatch") -> list[CPDResult]:
        """Single-device window loop: one dispatch + one active-mask host
        sync per check window (the pre-pod contract)."""
        carry = prep.carry
        B, N = prep.batch, len(prep.shape)
        fits_dev: list = []
        host_syncs = 0
        it = 0
        tr = obs_trace.active()
        while it < prep.max_iters:
            k = min(self.check_every, prep.max_iters - it)
            fn = _build_batched_block(
                self.backend, N, self.rank, prep.shape, prep.cap, B,
                self.interpret, self.donate, self.solver, k,
                prep.pallas_meta, prep.method,
            )
            # Per-window dispatch + active-mask sync: the disabled branch
            # pays one global read and zero allocations.
            if tr is None:
                carry, fits_blk = fn(carry, prep.mode_data_all,
                                     prep.fit_data, prep.tol_dev,
                                     prep.max_iters_dev)
                any_active = bool(np.any(jax.device_get(carry[1])))
            else:
                with tr.span("batched.window", cat="serve",
                             backend=self.backend, B=B, sweeps=k,
                             method=prep.method):
                    carry, fits_blk = fn(carry, prep.mode_data_all,
                                         prep.fit_data, prep.tol_dev,
                                         prep.max_iters_dev)
                    any_active = bool(np.any(jax.device_get(carry[1])))
            fits_dev.append(fits_blk)
            it += k
            host_syncs += 1          # the only in-loop sync: the active mask
            if not any_active:
                break
        _LEDGER.count(self._kind, dispatches=len(fits_dev))

        host_syncs += 1              # final materialization
        fits_cat = (jnp.concatenate(fits_dev, axis=0) if fits_dev
                    else jnp.zeros((0, B), jnp.float32))   # n_iters <= 0
        return self._materialize(prep, carry, fits_cat, host_syncs,
                                 engine="batched")

    def _execute_pod(self, prep: "_PreparedBatch") -> list[CPDResult]:
        """Pod path: the whole multi-window run is ONE shard_map dispatch;
        convergence is judged on device (``lax.while_loop`` + mesh psum),
        so the only host sync is the final materialization."""
        B, N = prep.batch, len(prep.shape)
        n_dev = self.num_devices
        per_dev = B // n_dev
        max_windows = -(-prep.max_iters // self.check_every)
        if max_windows == 0:                       # n_iters <= 0
            return self._materialize(
                prep, prep.carry, jnp.zeros((0, B), jnp.float32), 1,
                engine="pod")
        fn = _build_pod_block(
            self.mesh, self.backend, N, self.rank, prep.shape, prep.cap,
            per_dev, self.interpret, self.donate, self.solver,
            self.check_every, max_windows, prep.pallas_meta, prep.method,
        )
        # Per-device request load for the dispatch span: lane i lands on
        # device i // per_dev (shard_map splits the leading axis into
        # contiguous blocks).  lane_nnz is already in lane (placed)
        # order; when placement ran, also record the arrival-order
        # counterfactual so the balance win is visible in the trace.
        dev_nnz = plan_mod.pod_device_nnz(prep.lane_nnz, n_dev)
        placement = {"lane_placement": "contiguous"}
        if prep.lane_of is not None:
            arrival = [prep.lane_nnz[prep.lane_of[i]] for i in range(B)]
            placement = {
                "lane_placement": "balanced",
                "device_nnz_contiguous":
                    plan_mod.pod_device_nnz(arrival, n_dev),
                "imbalance": plan_mod.pod_imbalance(prep.lane_nnz, n_dev),
                "imbalance_contiguous":
                    plan_mod.pod_imbalance(arrival, n_dev),
            }
        _LEDGER.count(self._kind, dispatches=1)
        tr = obs_trace.active()
        if tr is None:
            carry, fits_buf, windows = fn(
                prep.carry, prep.mode_data_all, prep.fit_data,
                prep.tol_dev, prep.max_iters_dev)
            res = self._materialize(prep, carry, fits_buf, 1, engine="pod")
        else:
            with tr.span("pod.dispatch", cat="serve",
                         backend=self.backend, B=B, devices=n_dev,
                         B_per_device=per_dev, max_windows=max_windows,
                         sweeps_per_window=self.check_every,
                         nnz_cap=prep.cap, device_nnz=dev_nnz,
                         method=prep.method, **placement):
                carry, fits_buf, windows = fn(
                    prep.carry, prep.mode_data_all, prep.fit_data,
                    prep.tol_dev, prep.max_iters_dev)
                res = self._materialize(prep, carry, fits_buf, 1,
                                        engine="pod")
            # Window count is only known after the fetch (the loop ran
            # entirely on device) — record it as one aggregate event, not
            # per-window spans: there were no per-window host syncs to
            # hang spans off, which is the point.
            obs_trace.event("pod.window", cat="serve",
                            windows=int(windows), devices=n_dev,
                            B_per_device=per_dev,
                            sweeps_per_window=self.check_every)
        return res

    def _materialize(self, prep: "_PreparedBatch", carry, fits_cat,
                     host_syncs: int, engine: str) -> list[CPDResult]:
        """One batched device_get for everything; pod padding lanes (the
        repeated trailing requests) are dropped here."""
        N = len(prep.shape)
        state, _, _, done = carry
        factors_h, weights_h, done_h, fits_h = jax.device_get(
            (state[0], state[2], done, fits_cat))
        wall = obs_clock.now() - prep.t_start

        results = []
        for i in range(prep.requested):
            li = prep.lane_of[i] if prep.lane_of is not None else i
            ni = int(done_h[li])
            results.append(CPDResult(
                factors=[np.asarray(factors_h[d][li]) for d in range(N)],
                weights=np.asarray(weights_h[li], dtype=np.float64),
                fits=[float(f) for f in fits_h[:ni, li]],
                iters=ni,
                mttkrp_seconds=0.0,
                total_seconds=wall,
                host_syncs=host_syncs,
                engine=engine,
                method=prep.method,
            ))
        return results


@dataclasses.dataclass
class _PreparedBatch:
    """Host-assembled batch, ready to dispatch (see ``prepare_batch``).
    ``batch`` >= ``requested`` on the pod path (mesh-multiple padding);
    only the first ``requested`` lanes materialize into results."""

    requested: int
    batch: int
    shape: tuple[int, ...]
    cap: int
    method: str
    carry: tuple
    mode_data_all: tuple
    fit_data: tuple
    tol_dev: jnp.ndarray
    max_iters_dev: jnp.ndarray
    max_iters: int
    pallas_meta: tuple | None
    lane_nnz: list[int]
    # order[lane] inverse from load-aware placement: request i lives in
    # lane lane_of[i].  None when lanes are in arrival order.
    lane_of: list[int] | None
    t_start: float
    h2d_bytes: int          # host arrays uploaded for the batch
