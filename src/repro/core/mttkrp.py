"""spMTTKRP over mode-specific layouts: the one MTTKRP dispatch.

Backends:
  'segment' — vectorized jnp: fused gather–Hadamard–segment_sum on the
              sorted layout.  Production CPU path and kernel oracle.
  'pallas'  — the TPU Pallas kernel (interpreted on the CPU backend only).
  'coo'     — unsorted elementwise formulation (naive baseline; materializes
              the (nnz, R) intermediate the paper eliminates).

This module is the only place that knows three things: which arrays each
backend's per-mode data holds (``MODE_FIELDS``), how each kernel is
called, and how the kernel's relabeled output rows are scattered back to
original rows.  ``mttkrp_mode`` is that dispatch; the fused, batched and
distributed sweeps (``core.als_device.build_sweep_fn``), the one-shot
``mttkrp`` below and ``core.distributed.mttkrp_distributed`` all run it.
Every path returns the output factor in ORIGINAL row order, f32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.mttkrp_pallas import mttkrp_pallas
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from . import plan as plan_mod
from .coo import SparseTensor
from .layout import ModeLayout, build_all_mode_layouts
from .load_balance import Scheme

# Each backend's mode data, in order: (value-baked, structural).  The
# value-baked tuple carries the tensor's values; the structural one
# carries the canonical->layout permutation instead and takes fresh
# values at every call (methods whose values change each sweep, such as
# masked).  coo's arrays are the canonical COO list, shared by all modes.
MODE_FIELDS = {
    "segment": (("idx", "rows", "vals", "row_perm"),
                ("idx", "rows", "row_perm", "perm")),
    "pallas": (("rb_of", "first", "idx_packed", "vals_packed",
                "lrows_packed", "row_perm"),
               ("rb_of", "first", "idx_packed", "lrows_packed", "row_perm",
                "perm", "val_scatter")),
    "coo": (("indices", "values"), ("indices",)),
}


def mode_fields(backend: str, valued: bool = False) -> tuple[str, ...]:
    """The names of ``backend``'s mode-data arrays, in tuple order."""
    if backend not in MODE_FIELDS:
        raise ValueError(f"unknown backend {backend!r}")
    return MODE_FIELDS[backend][bool(valued)]


def _host_array(name: str, layout: ModeLayout,
                packed: kops.PackedModeLayout | None,
                tensor: SparseTensor | None = None) -> np.ndarray:
    """One mode-data array on the host, by its ``MODE_FIELDS`` name."""
    if name == "idx":
        return layout.indices[:, layout.input_modes()]
    if name == "rows":
        return layout.rows
    if name == "vals":
        return layout.values.astype(np.float32)
    if name == "row_perm":
        return layout.row_perm
    if name == "perm":
        return layout.perm.astype(np.int32)
    if name == "indices":
        return tensor.indices
    if name == "values":
        return tensor.values.astype(np.float32)
    return getattr(packed, name)


def layout_arrays(backend: str, layout: ModeLayout,
                  packed: kops.PackedModeLayout | None = None, *,
                  valued: bool = False) -> tuple[np.ndarray, ...]:
    """Host arrays of one layout's mode data (segment or Pallas): what a
    caller that stacks its own layouts (``serve.batched_engine``) ships."""
    return tuple(_host_array(n, layout, packed)
                 for n in mode_fields(backend, valued))


def to_original_rows(out: jnp.ndarray, row_perm) -> jnp.ndarray:
    """Relabeled -> original rows: ``out[row_perm[i]] = out_rel[i]``;
    ``row_perm=None`` (coo) means the rows are original already."""
    if row_perm is None:
        return out
    return jnp.zeros_like(out).at[row_perm].set(out)


def psum_combine(axis: str) -> Callable:
    """The mesh combine of both partition schemes: sum every device's
    partial output over ``axis``, then scatter to original rows."""

    def combine(out, row_perm):
        return to_original_rows(lax.psum(out, axis), row_perm)

    return combine


def owned_rows_combine(axis: str, own_rows, gather_dst,
                       num_rows: int) -> Callable:
    """The scheme-1 "gather" combine: a partial has support only on this
    device's owned relabeled rows (``core.plan.DeviceShards.own_rows``),
    so all-gather those slices with their original-row destinations and
    scatter them into a buffer whose dummy row ``num_rows`` absorbs the
    padding slots.  Moves ~1/kappa of the psum's payload."""

    def combine(out, row_perm):
        own = out[own_rows]                        # (rows_cap, R)
        g_vals = lax.all_gather(own, axis)         # (κ, cap, R)
        g_dst = lax.all_gather(gather_dst, axis)   # (κ, cap)
        full = jnp.zeros((num_rows + 1, out.shape[-1]), out.dtype)
        full = full.at[g_dst.reshape(-1)].set(
            g_vals.reshape(-1, out.shape[-1]))
        return full[:num_rows]

    return combine


def layout_values(backend: str, mode_data: tuple, vals: jnp.ndarray):
    """Canonical-order values in the order ``mttkrp_mode`` takes them with
    structural mode data: layout order for segment and Pallas, canonical
    for coo."""
    if backend == "coo":
        return vals
    perm = mode_data[mode_fields(backend, True).index("perm")]
    return vals[perm]


def mttkrp_mode(backend: str, mode: int, mode_data: tuple, factors,
                num_rows: int, *, meta: tuple | None = None,
                interpret: bool | None = None, vals=None,
                combine: Callable | None = None) -> jnp.ndarray:
    """One mode's MTTKRP on the device: ``(num_rows, R)`` f32 in original
    row order.

    ``mode_data`` is ``backend``'s tuple for ``mode`` (``MODE_FIELDS``):
    value-baked when ``vals`` is None, else structural, with ``vals`` in
    layout order for segment and Pallas (``layout_values``) and canonical
    order for coo.  Trailing arrays beyond the backend's fields are left
    to ``combine``.  ``factors`` are all N factors; ``meta`` is the
    mode's Pallas tiling ``(num_row_blocks, block_rows, tile,
    rank_block)``.  ``combine(out, row_perm)``, a mesh caller's
    cross-device step, takes the kernel's partial output in relabeled
    rows and returns the combined output in original rows
    (``psum_combine``, ``owned_rows_combine``); without it the output is
    only scattered to original rows.
    """
    f = dict(zip(mode_fields(backend, vals is not None), mode_data))
    if backend == "coo":
        out = kref.mttkrp_coo(f["indices"], f["values"] if vals is None
                              else vals, list(factors), mode, num_rows)
        row_perm = None
    else:
        in_f = [factors[w] for w in range(len(factors)) if w != mode]
        if backend == "segment":
            out = kref.mttkrp_sorted_segments(
                f["idx"], f["rows"], f["vals"] if vals is None else vals,
                in_f, num_rows)
        else:
            idxp = f["idx_packed"]
            if vals is None:
                valsp = f["vals_packed"]
            else:
                valsp = jnp.zeros((1, idxp.shape[-1]), jnp.float32)
                valsp = valsp.at[0, f["val_scatter"]].set(vals)
            nrb, br, tile, rblk = meta
            out = mttkrp_pallas(
                f["rb_of"], f["first"], idxp, valsp, f["lrows_packed"],
                in_f, num_row_blocks=nrb, block_rows=br, tile=tile,
                rank_block=rblk, interpret=interpret,
            )[:num_rows]
        row_perm = f["row_perm"]
    if combine is not None:
        return combine(out, row_perm)
    return to_original_rows(out, row_perm)


def _upload(what: str, mode: int | None, arrays) -> tuple:
    """Upload host arrays for a plan cache, once: a ``plan.upload`` span
    and the ledger's ``plan`` count carry the bytes."""
    with obs_trace.span("plan.upload", cat="plan", what=what,
                        mode=mode) as sp:
        out = tuple(jnp.asarray(a) for a in arrays)
        nbytes = sum(int(x.nbytes) for x in out)
        sp.set(h2d_bytes=nbytes)
    _LEDGER.count("plan", h2d_bytes=nbytes)
    return out


@dataclasses.dataclass
class MTTKRPPlan:
    """Preprocessing product: all mode copies + (lazily) packed slabs.

    This is the paper's "mode-specific tensor format": built once, reused
    for every ALS iteration along every mode.  A plan without layouts
    serves the coo backend, which reads none.  When a ``partition``
    (``core.plan.PartitionPlan``) is attached, every packing follows its
    static per-mode decisions — same plan in, same array shapes out, which
    is what lets the sequential path produce bit-identical results to the
    plan's vmapped and distributed consumers.
    """

    tensor: SparseTensor
    kappa: int
    layouts: list[ModeLayout]
    assignment: str = "greedy"
    block_rows: int = kops.DEFAULT_BLOCK_ROWS
    tile: int = kops.DEFAULT_TILE
    partition: plan_mod.PartitionPlan | None = None
    _packed: dict[int, kops.PackedModeLayout] = dataclasses.field(default_factory=dict)
    # (mode, name) -> device array; coo's arrays sit under mode None.
    _dev: dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)

    def packed(self, mode: int) -> kops.PackedModeLayout:
        if mode not in self._packed:
            if self.partition is not None:
                mp = self.partition.modes[mode]
                self._packed[mode] = kops.pack_layout(
                    self.layouts[mode], block_rows=mp.block_rows,
                    tile=mp.tile, num_slabs_cap=mp.slab_cap,
                )
            else:
                self._packed[mode] = kops.pack_layout(
                    self.layouts[mode], block_rows=self.block_rows,
                    tile=self.tile,
                )
        return self._packed[mode]

    def mode_plan(self, mode: int, rank: int) -> plan_mod.ModePlan:
        """The static per-mode plan this tensor executes under: the
        attached partition plan when present (bucket semantics), else a
        per-layout plan pinned to the actual packing's tiling.  All
        rank-block decisions flow through here (core.plan's cost model)."""
        if self.partition is not None and self.partition.rank == rank:
            return self.partition.modes[mode]
        p = self.packed(mode)
        return plan_mod.plan_layout(self.layouts[mode], rank,
                                    block_rows=p.block_rows, tile=p.tile)

    def mode_data(self, mode: int, backend: str,
                  valued: bool = False) -> tuple:
        """``backend``'s mode data for ``mode`` as device arrays.  Each
        array is uploaded once and kept, and tuples that name the same
        array (``row_perm`` of segment and Pallas, say) share it."""
        key = None if backend == "coo" else mode
        names = mode_fields(backend, valued)
        missing = [n for n in names if (key, n) not in self._dev]
        if missing:
            lay = None if key is None else self.layouts[mode]
            packed = self.packed(mode) if backend == "pallas" else None
            up = _upload(backend, key, [
                _host_array(n, lay, packed, self.tensor) for n in missing])
            self._dev.update(((key, n), x) for n, x in zip(missing, up))
        return tuple(self._dev[(key, n)] for n in names)

    def device_cache(self) -> tuple:
        """Every device array the plan has uploaded and keeps."""
        return tuple(self._dev.values())


def make_plan(
    tensor: SparseTensor,
    kappa: int,
    *,
    scheme: Scheme | None = None,
    assignment: str = "greedy",
    policy: str = "threshold",
    block_rows: int = kops.DEFAULT_BLOCK_ROWS,
    tile: int = kops.DEFAULT_TILE,
    partition: plan_mod.PartitionPlan | None = None,
) -> MTTKRPPlan:
    with obs_trace.span("plan.layouts", cat="plan", nnz=tensor.nnz,
                        nmodes=tensor.nmodes, kappa=kappa):
        layouts = build_all_mode_layouts(
            tensor, kappa, scheme=scheme, assignment=assignment,
            policy=policy)
    return MTTKRPPlan(
        tensor=tensor,
        kappa=kappa,
        layouts=layouts,
        assignment=assignment,
        block_rows=block_rows,
        tile=tile,
        partition=partition,
    )


def collect_mode_data(plan: MTTKRPPlan, backend: str, rank: int, *,
                      valued: bool = False):
    """Every mode's data for ``backend`` (from the plan's cache) and, for
    Pallas, the per-mode static tiling (``None`` otherwise)."""
    N = plan.tensor.nmodes
    datas = tuple(plan.mode_data(d, backend, valued) for d in range(N))
    if backend != "pallas":
        return datas, None
    return datas, tuple(plan.mode_plan(d, rank).pallas_meta
                        for d in range(N))


_mttkrp_jit = jax.jit(mttkrp_mode, static_argnames=(
    "backend", "mode", "num_rows", "meta", "interpret"))


def mttkrp(
    plan: MTTKRPPlan,
    factors: Sequence[jnp.ndarray],
    mode: int,
    *,
    backend: str = "segment",
    interpret: bool | None = None,
    rank_block: int | None = None,
) -> jnp.ndarray:
    """MTTKRP along ``mode``: returns (I_mode, R) f32 in original row order."""
    meta = None
    if backend == "pallas":
        meta = plan.mode_plan(mode, int(factors[0].shape[1])).pallas_meta
        if rank_block is not None:
            meta = meta[:3] + (rank_block,)
    return _mttkrp_jit(backend, mode, plan.mode_data(mode, backend),
                       tuple(jnp.asarray(f) for f in factors),
                       plan.layouts[mode].num_rows, meta=meta,
                       interpret=interpret)


def mttkrp_dense_ref(tensor: SparseTensor, factors: Sequence[np.ndarray], mode: int) -> np.ndarray:
    return kref.mttkrp_dense(tensor, list(factors), mode)
