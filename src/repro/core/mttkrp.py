"""spMTTKRP engines over mode-specific layouts.

Backends:
  'segment' — vectorized jnp: fused gather–Hadamard–segment_sum on the
              sorted layout.  Production CPU path and kernel oracle.
  'pallas'  — the TPU Pallas kernel (interpreted on the CPU backend only).
  'coo'     — unsorted elementwise formulation (naive baseline; materializes
              the (nnz, R) intermediate the paper eliminates).

All backends return the output factor in ORIGINAL row order, f32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.mttkrp_pallas import mttkrp_pallas
from ..obs import trace as obs_trace
from ..obs.ledger import LEDGER as _LEDGER
from . import plan as plan_mod
from .coo import SparseTensor
from .layout import ModeLayout, build_all_mode_layouts
from .load_balance import Scheme


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
    for every ALS iteration along every mode.  When a ``partition``
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
    _dev_arrays: dict[int, tuple] = dataclasses.field(default_factory=dict)
    _dev_packed: dict[int, tuple] = dataclasses.field(default_factory=dict)
    _dev_coo: tuple | None = None

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

    def device_arrays(self, mode: int):
        """Layout arrays as jnp device arrays (cached)."""
        if mode not in self._dev_arrays:
            lay = self.layouts[mode]
            in_modes = lay.input_modes()
            self._dev_arrays[mode] = _upload("arrays", mode, (
                lay.indices[:, in_modes], lay.rows, lay.values,
                lay.row_perm))
        return self._dev_arrays[mode]

    def device_packed(self, mode: int) -> tuple:
        """Packed slab arrays as jnp device arrays (cached): uploaded once,
        reused by every pallas-backend call and the fused ALS engine."""
        if mode not in self._dev_packed:
            p = self.packed(mode)
            self._dev_packed[mode] = _upload("packed", mode, (
                p.rb_of, p.first, p.idx_packed, p.vals_packed,
                p.lrows_packed))
        return self._dev_packed[mode]

    def device_coo(self) -> tuple:
        """COO indices/values as jnp device arrays (cached): the coo backend
        previously re-uploaded both from host numpy on every call."""
        if self._dev_coo is None:
            self._dev_coo = _upload("coo", None, (self.tensor.indices,
                                                  self.tensor.values))
        return self._dev_coo

    def device_cache(self) -> tuple:
        """Every device array the plan has uploaded and keeps."""
        return (self._dev_arrays, self._dev_packed, self._dev_coo)


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


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _segment_backend(input_indices, rows, values, factors, row_perm, num_rows):
    out_rel = kref.mttkrp_sorted_segments(
        input_indices, rows, values, list(factors), num_rows
    )
    # relabeled -> original rows: out[row_perm[i]] = out_rel[i]
    return jnp.zeros_like(out_rel).at[row_perm].set(out_rel)


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "num_row_blocks", "block_rows", "tile", "rank_block",
    "interpret"))
def _pallas_backend(rb_of, first, idxp, valsp, lrowsp, factors, row_perm,
                    num_rows, num_row_blocks, block_rows, tile, rank_block,
                    interpret):
    out_rel = mttkrp_pallas(
        rb_of, first, idxp, valsp, lrowsp, list(factors),
        num_row_blocks=num_row_blocks, block_rows=block_rows, tile=tile,
        rank_block=rank_block, interpret=interpret,
    )[:num_rows]
    return jnp.zeros_like(out_rel).at[row_perm].set(out_rel)


@functools.partial(jax.jit, static_argnames=("mode", "num_rows"))
def _coo_backend(indices, values, factors, mode, num_rows):
    return kref.mttkrp_coo(indices, values, list(factors), mode, num_rows)


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
    lay = plan.layouts[mode]
    in_modes = lay.input_modes()
    in_factors = [factors[w] for w in in_modes]

    if backend == "segment":
        idx, rows, vals, row_perm = plan.device_arrays(mode)
        return _segment_backend(
            idx, rows, vals, tuple(in_factors), row_perm, lay.num_rows
        )
    if backend == "pallas":
        packed = plan.packed(mode)
        if rank_block is None:
            rank = int(in_factors[0].shape[1])
            rank_block = plan.mode_plan(mode, rank).rank_block
        return _pallas_backend(
            *plan.device_packed(mode), tuple(in_factors),
            jnp.asarray(lay.row_perm), num_rows=packed.num_rows,
            num_row_blocks=packed.num_row_blocks,
            block_rows=packed.block_rows, tile=packed.tile,
            rank_block=rank_block, interpret=interpret,
        )
    if backend == "coo":
        indices, values = plan.device_coo()
        return _coo_backend(
            indices, values,
            tuple(jnp.asarray(f) for f in factors),
            mode, lay.num_rows,
        )
    raise ValueError(f"unknown backend {backend!r}")


def mttkrp_dense_ref(tensor: SparseTensor, factors: Sequence[np.ndarray], mode: int) -> np.ndarray:
    return kref.mttkrp_dense(tensor, list(factors), mode)
