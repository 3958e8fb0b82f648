"""Jit'd wrappers + host-side slab packing for the Pallas MTTKRP kernel.

``pack_slabs`` converts a row-sorted mode layout into the fixed-shape slab
arrays the kernel consumes.  Packing is one-time host preprocessing per
mode copy (amortized over all ALS iterations), mirroring the paper's
format-construction stage.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import trace as obs_trace
from . import ref as ref_mod
from .mttkrp_pallas import GATHER_CHUNK, VMEM_LIMIT_BYTES, mttkrp_pallas

DEFAULT_TILE = 256
DEFAULT_BLOCK_ROWS = 128


@dataclasses.dataclass(frozen=True)
class PackedModeLayout:
    """Device-ready slab packing of one mode layout (or one partition of it).

    Shapes: G grid steps, T = tile nonzeros per slab, W input modes.
    """

    mode: int
    num_rows: int              # relabeled rows covered (<= num_row_blocks*BR)
    num_row_blocks: int
    block_rows: int
    tile: int
    rb_of: np.ndarray          # (G,) int32
    first: np.ndarray          # (G,) int32
    idx_packed: np.ndarray     # (W, G*T) int32
    vals_packed: np.ndarray    # (1, G*T) float32
    lrows_packed: np.ndarray   # (1, G*T) int32
    input_modes: tuple[int, ...]
    pad_fraction: float        # padding overhead (diagnostic)
    num_real_slabs: int = -1   # slabs before cap padding (-1: no padding)
    # (nnz,) int32: flat position in vals_packed[0] of each *layout-order*
    # entry.  Entries map to exactly one valid slot, so scattering a fresh
    # value vector through this map rebuilds vals_packed on device — the
    # mask-weighted MTTKRP path re-threads per-sweep residual values
    # through the SAME packed slabs without repacking on host.
    val_scatter: np.ndarray | None = None
    # (1, G*T) float32 per-entry observation weights packed alongside the
    # values (None when the layout is unweighted).  Padding slots carry
    # weight 0 — the SAME exact-no-op mechanism slab/nnz padding uses, now
    # general: any entry the caller down-weights to 0 vanishes from the
    # accumulation bit-exactly.
    wts_packed: np.ndarray | None = None

    @property
    def num_slabs(self) -> int:
        return int(self.rb_of.shape[0])

    def weighted_vals(self) -> np.ndarray:
        """Kernel-ready weighted values: ``vals_packed * wts_packed`` (or
        ``vals_packed`` unchanged for an unweighted packing).  Feeding
        these to the kernel computes the weighted MTTKRP with zero extra
        device work — weights are folded at pack time."""
        if self.wts_packed is None:
            return self.vals_packed
        return (self.vals_packed * self.wts_packed).astype(np.float32)

    @property
    def bucket_key(self) -> tuple:
        """Static identity of this packing's shapes: every packed layout
        with the same key has identical array shapes, so bucket-mates
        stack along a new leading axis (the vmapped Pallas path)."""
        return (self.mode, self.num_rows, self.num_row_blocks,
                self.block_rows, self.tile, self.num_slabs,
                self.input_modes)


def pack_slabs(
    input_indices: np.ndarray,   # (nnz, W) int32 — input-mode columns only
    rows: np.ndarray,            # (nnz,) int32 — relabeled rows, sorted
    values: np.ndarray,          # (nnz,)
    num_rows: int,
    *,
    mode: int = 0,
    input_modes: Sequence[int] = (),
    block_rows: int = DEFAULT_BLOCK_ROWS,
    tile: int = DEFAULT_TILE,
    num_slabs_cap: int | None = None,
    weights: np.ndarray | None = None,
) -> PackedModeLayout:
    """Pack row-sorted COO data into per-row-block slabs of ``tile`` nonzeros.

    Every row block gets >= 1 slab (empty blocks get one all-padding slab so
    their output block is zero-initialized).  Padding entries carry value 0
    and indices 0, contributing nothing.

    ``weights`` — optional per-entry observation weights aligned with
    ``values`` (layout order).  They are packed into ``wts_packed`` through
    the identical slab placement (padding slots get weight 0), so
    ``weighted_vals()`` is the weighted kernel input.

    ``num_slabs_cap`` (from ``core.plan.slab_cap``) pads the grid with
    appended all-zero slabs on the LAST row block, making the packed array
    shapes a pure function of the plan rather than the data: bucket-mates
    stack for ``jax.vmap``.  The padding is bit-exact — the real slabs are
    untouched (appending cannot shift slab boundaries) and each extra slab
    contributes ``+= 0.0`` to an already-initialized output block.
    """
    tr = obs_trace.active()
    if tr is None:
        return _pack_slabs_impl(
            input_indices, rows, values, num_rows, mode=mode,
            input_modes=input_modes, block_rows=block_rows, tile=tile,
            num_slabs_cap=num_slabs_cap, weights=weights)
    with tr.span("pack.slabs", cat="kernels", mode=int(mode),
                 nnz=len(values), num_rows=int(num_rows),
                 block_rows=int(block_rows), tile=int(tile)) as sp:
        p = _pack_slabs_impl(
            input_indices, rows, values, num_rows, mode=mode,
            input_modes=input_modes, block_rows=block_rows, tile=tile,
            num_slabs_cap=num_slabs_cap, weights=weights)
        sp.set(slabs=p.num_slabs, real_slabs=p.num_real_slabs,
               pad_fraction=round(p.pad_fraction, 4))
        return p


def _pack_slabs_impl(
    input_indices: np.ndarray,
    rows: np.ndarray,
    values: np.ndarray,
    num_rows: int,
    *,
    mode: int = 0,
    input_modes: Sequence[int] = (),
    block_rows: int = DEFAULT_BLOCK_ROWS,
    tile: int = DEFAULT_TILE,
    num_slabs_cap: int | None = None,
    weights: np.ndarray | None = None,
) -> PackedModeLayout:
    nnz = len(values)
    if nnz and not bool(np.all(rows[:-1] <= rows[1:])):
        raise ValueError("rows must be sorted (build via core.layout)")
    W = input_indices.shape[1]
    nb = max(1, -(-num_rows // block_rows))
    row_ptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=num_rows), out=row_ptr[1:])
    starts = row_ptr[np.minimum(np.arange(nb) * block_rows, num_rows)]
    ends = row_ptr[np.minimum((np.arange(nb) + 1) * block_rows, num_rows)]
    lens = ends - starts
    slabs_per_block = np.maximum(1, -(-lens // tile))
    G = int(slabs_per_block.sum())

    slab_block = np.repeat(np.arange(nb, dtype=np.int64), slabs_per_block)
    # Rank of each slab within its block.
    block_start_slab = np.zeros(nb, dtype=np.int64)
    np.cumsum(slabs_per_block[:-1], out=block_start_slab[1:])
    rank = np.arange(G, dtype=np.int64) - block_start_slab[slab_block]

    src_start = starts[slab_block] + rank * tile
    length = np.clip(ends[slab_block] - src_start, 0, tile)
    src = src_start[:, None] + np.arange(tile, dtype=np.int64)[None, :]
    valid = np.arange(tile)[None, :] < length[:, None]
    src_c = np.minimum(src, max(nnz - 1, 0))

    if weights is not None and len(weights) != nnz:
        raise ValueError(
            f"weights length {len(weights)} != nnz {nnz}")
    wts_p = None
    if nnz:
        vals_p = np.where(valid, values[src_c], 0).astype(np.float32)
        if weights is not None:
            wts_p = np.where(valid, weights[src_c], 0).astype(np.float32)
        idx_p = np.where(valid[:, :, None], input_indices[src_c], 0).astype(np.int32)
        lrow_p = np.where(
            valid, rows[src_c] - slab_block[:, None] * block_rows, 0
        ).astype(np.int32)
        # Invert the (layout entry -> packed slot) placement: slabs tile
        # each row block's [start, end) range contiguously, so every layout
        # position lands in exactly one valid slot.  Cap padding appends
        # whole slabs, which leaves these flat positions untouched.
        flat = (np.arange(G, dtype=np.int64)[:, None] * tile
                + np.arange(tile, dtype=np.int64)[None, :])
        val_scatter = np.empty(nnz, dtype=np.int32)
        val_scatter[src[valid]] = flat[valid].astype(np.int32)
    else:
        vals_p = np.zeros((G, tile), np.float32)
        if weights is not None:
            wts_p = np.zeros((G, tile), np.float32)
        idx_p = np.zeros((G, tile, W), np.int32)
        lrow_p = np.zeros((G, tile), np.int32)
        val_scatter = np.zeros(0, dtype=np.int32)

    G_real = G
    if num_slabs_cap is not None:
        if G > num_slabs_cap:
            raise ValueError(
                f"packing needs {G} slabs but the plan caps at "
                f"{num_slabs_cap}; nnz exceeds the plan's nnz_cap")
        extra = num_slabs_cap - G
        if extra:
            # Appended zero slabs revisit the last row block: first=0 (no
            # re-init), values 0, local row 0 — an exact += 0.0.
            slab_block = np.concatenate(
                [slab_block, np.full(extra, nb - 1, dtype=np.int64)])
            rank = np.concatenate(
                [rank, np.ones(extra, dtype=np.int64)])   # never first
            vals_p = np.concatenate(
                [vals_p, np.zeros((extra, tile), np.float32)])
            if wts_p is not None:
                wts_p = np.concatenate(
                    [wts_p, np.zeros((extra, tile), np.float32)])
            idx_p = np.concatenate(
                [idx_p, np.zeros((extra, tile, W), np.int32)])
            lrow_p = np.concatenate(
                [lrow_p, np.zeros((extra, tile), np.int32)])
            G = num_slabs_cap

    pad = 1.0 - (nnz / float(G * tile)) if G else 0.0
    return PackedModeLayout(
        mode=mode,
        num_rows=num_rows,
        num_row_blocks=nb,
        block_rows=block_rows,
        tile=tile,
        rb_of=slab_block.astype(np.int32),
        first=(rank == 0).astype(np.int32),
        idx_packed=np.ascontiguousarray(
            idx_p.reshape(G * tile, W).T.astype(np.int32)
        ),
        vals_packed=vals_p.reshape(1, G * tile),
        lrows_packed=lrow_p.reshape(1, G * tile).astype(np.int32),
        input_modes=tuple(input_modes) or tuple(range(W)),
        pad_fraction=float(pad),
        num_real_slabs=G_real,
        val_scatter=val_scatter,
        wts_packed=(None if wts_p is None
                    else wts_p.reshape(1, G * tile).astype(np.float32)),
    )


def pack_layout(layout, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                tile: int = DEFAULT_TILE,
                num_slabs_cap: int | None = None,
                weights: np.ndarray | None = None) -> PackedModeLayout:
    """Pack a ``core.layout.ModeLayout`` for kernel execution.

    With ``num_slabs_cap`` (see ``core.plan``) the packing is padded to the
    plan's static grid size — bucket-keyed: every layout of the same
    (shape, nnz-bucket) class yields identically-shaped arrays.

    ``weights`` — per-entry observation weights in CANONICAL COO order
    (the front-door contract); the layout's permutation maps them to the
    packed slots alongside the values."""
    in_modes = layout.input_modes()
    return pack_slabs(
        layout.indices[:, in_modes],
        layout.rows,
        layout.values,
        layout.num_rows,
        mode=layout.mode,
        input_modes=in_modes,
        block_rows=block_rows,
        tile=tile,
        num_slabs_cap=num_slabs_cap,
        weights=(None if weights is None
                 else np.asarray(weights, np.float32)[layout.perm]),
    )


# -- beyond-paper: BlockSpec auto-tuning -------------------------------------
#
# The cost model below is consumed through ``core.plan`` (the single
# planning layer): ``plan_bucket`` prices candidate tilings against a
# uniform-distribution stand-in, ``plan_layout`` against the real layout.
# ``estimate_pack_cost``/``auto_tiles`` accept either — they only read
# ``num_rows`` / ``nnz`` / ``nmodes`` / ``row_ptr``.

_MXU_DIM = 128
_LANES = 128
_SUBLANES = 8
# Planning budget: three quarters of the kernel's scoped VMEM limit, the
# rest left to Mosaic's own scratch.
_VMEM_BYTES = VMEM_LIMIT_BYTES * 3 // 4
_STEP_OVERHEAD_SLOTS = 192   # pipeline bubble per grid step, in slot units


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def tile_candidates():
    """(block_rows, tile) pairs the compiled kernel accepts: a slab block
    is (W, tile) / (1, tile), so ``tile`` must be a lane multiple; the
    output block is (block_rows, rank_block), so ``block_rows`` a sublane
    multiple."""
    return [(br, t) for br in (8, 32, 128, 256) for t in (128, 256, 512)]


def kernel_vmem_bytes(block_rows: int, tile: int, rank_block: int,
                      factor_rows: int, num_inputs: int) -> int:
    """VMEM the kernel needs for one (block_rows, tile, rank_block)
    choice: every pipelined block twice (Pallas double-buffers inputs and
    output), padded to the (8, 128) f32 tiling — a rank block narrower
    than 128 lanes still occupies 128 — plus the in-kernel working set
    (the one-hot gather and scatter operands and the Hadamard product).
    Each factor is padded by less than ``GATHER_CHUNK`` rows."""
    lanes = _round_up(rank_block, _LANES)
    slabs = (_round_up(num_inputs, _SUBLANES) + 2 * _SUBLANES) * tile * 4
    factors = (factor_rows + GATHER_CHUNK * num_inputs) * lanes * 4
    out = block_rows * lanes * 4
    work = tile * (GATHER_CHUNK + block_rows + 3 * lanes) * 4
    return 2 * (slabs + factors + out) + work


def _fit_rank_block(rank: int, block_rows: int, tile: int, factor_rows: int,
                    num_inputs: int, vmem_budget: int) -> int:
    """Widest rank block the compiled kernel accepts (R itself, or a
    multiple of 128 below R) that fits ``vmem_budget``; 0 if none does."""
    cands = [rank] + list(range(_round_up(rank, _LANES) - _LANES, 0,
                                -_LANES))
    for rb in cands:
        if kernel_vmem_bytes(block_rows, tile, rb, factor_rows,
                             num_inputs) <= vmem_budget:
            return rb
    return 0


def _no_fit_error(mode, rank: int, factor_rows: int, vmem_budget: int,
                  need: int) -> ValueError:
    return ValueError(
        f"pallas MTTKRP cannot plan mode {mode}: its input factors have "
        f"{factor_rows} rows in all, and at rank {rank} the narrowest "
        f"rank block needs {need} bytes of VMEM against a budget of "
        f"{vmem_budget} bytes; use backend='segment' for this tensor")


def auto_rank_block(rank: int, block_rows: int, tile: int, factor_rows: int,
                    num_inputs: int, *, vmem_budget: int = _VMEM_BYTES,
                    mode: int | None = None) -> int:
    """Rank block for the kernel: ``rank`` when the whole rank fits the
    VMEM budget (no tiling), else the widest multiple of 128 that does.
    Raises ``ValueError`` naming ``mode``, the factor rows and the budget
    when not even one block fits."""
    rb = _fit_rank_block(rank, block_rows, tile, factor_rows, num_inputs,
                         vmem_budget)
    if rb == 0:
        need = kernel_vmem_bytes(block_rows, tile, min(rank, _LANES),
                                 factor_rows, num_inputs)
        raise _no_fit_error(mode, rank, factor_rows, vmem_budget, need)
    return rb


def estimate_pack_cost(layout, block_rows: int, tile: int, rank: int,
                       factor_rows: int, *,
                       vmem_budget: int = _VMEM_BYTES) -> dict:
    """Closed-form kernel cost for a (block_rows, tile) choice — no packing.

    slots      = sum over row blocks of ceil(len/tile)*tile  (incl. padding)
    mxu_factor = cost of the (tile x block_rows) scatter matmul relative to
                 a lane-saturated tile (block_rows < 128 wastes MXU columns;
                 block_rows > 128 adds proportional work)
    vmem       = ``kernel_vmem_bytes`` of the chosen rank block; when the
                 full rank does not fit, the rank dimension is tiled in
                 128-column blocks (grid (R_blocks, G)) and every rank
                 block re-streams the slabs, multiplying cost.
    """
    nb = max(1, -(-layout.num_rows // block_rows))
    row_ptr = layout.row_ptr
    starts = row_ptr[np.minimum(np.arange(nb) * block_rows, layout.num_rows)]
    ends = row_ptr[np.minimum((np.arange(nb) + 1) * block_rows,
                              layout.num_rows)]
    slabs = np.maximum(1, -(-(ends - starts) // tile))
    G = int(slabs.sum())
    slots = G * tile
    pad = 1.0 - layout.nnz / max(slots, 1)
    mxu_factor = max(block_rows, _MXU_DIM) / _MXU_DIM
    W = layout.nmodes - 1
    rank_block = _fit_rank_block(rank, block_rows, tile, factor_rows, W,
                                 vmem_budget)
    num_rank_blocks = -(-rank // rank_block) if rank_block else 0
    vmem = kernel_vmem_bytes(block_rows, tile, rank_block or rank,
                             factor_rows, W)
    cost = (slots * mxu_factor + G * _STEP_OVERHEAD_SLOTS) * max(
        num_rank_blocks, 1)
    return {"block_rows": block_rows, "tile": tile, "grid": G,
            "pad_fraction": pad, "vmem": int(vmem),
            "rank_block": int(rank_block),
            "num_rank_blocks": int(num_rank_blocks),
            "vmem_ok": bool(rank_block >= 1 and vmem <= vmem_budget),
            "cost": float(cost) if num_rank_blocks else float("inf")}


def auto_tiles(layout, rank: int = 32, factor_rows: int | None = None):
    """Pick (block_rows, tile) minimizing the modeled kernel cost under the
    VMEM budget.  The default (128, 256) is good for dense-ish modes; skewed
    or tiny modes prefer smaller row blocks (less slab padding).  Candidates
    whose factors only fit via rank tiling are costed with the re-streaming
    multiplier rather than rejected.  Raises ``ValueError`` naming the mode
    when no candidate fits the budget."""
    if factor_rows is None:
        factor_rows = sum(layout.shape[w] for w in layout.input_modes())
    best = None
    for br, t in tile_candidates():
        c = estimate_pack_cost(layout, br, t, rank, factor_rows)
        if not c["vmem_ok"]:
            continue
        if best is None or c["cost"] < best["cost"]:
            best = c
    if best is None:
        br, t = min(tile_candidates())
        need = kernel_vmem_bytes(br, t, min(rank, _LANES), factor_rows,
                                 layout.nmodes - 1)
        raise _no_fit_error(layout.mode, rank, factor_rows, _VMEM_BYTES,
                            need)
    return best["block_rows"], best["tile"]


def mttkrp_packed(
    packed: PackedModeLayout,
    factors: Sequence[jnp.ndarray],
    *,
    rank_block: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Run the Pallas kernel on a packed layout.  ``factors`` are the input
    factor matrices in ``packed.input_modes`` order.  Returns the relabeled
    (num_rows, R) f32 output (trailing padding rows stripped).

    A weighted packing (``pack_layout(weights=...)``) executes the
    WEIGHTED MTTKRP: the kernel consumes ``weighted_vals()`` — values
    pre-multiplied by their observation weights at the packed slots — so
    weight-0 entries vanish exactly with zero extra device work.

    ``rank_block=None`` auto-sizes the rank tile from the VMEM model: the
    full rank stays resident when it fits, else the widest feasible column
    block is used and the kernel makes one slab pass per rank block; a
    factor set that fits no block raises (see ``auto_rank_block``)."""
    if rank_block is None:
        rank = int(factors[0].shape[1])
        factor_rows = sum(int(f.shape[0]) for f in factors)
        rank_block = auto_rank_block(
            rank, packed.block_rows, packed.tile, factor_rows, len(factors),
            mode=packed.mode)
    out = mttkrp_pallas(
        jnp.asarray(packed.rb_of),
        jnp.asarray(packed.first),
        jnp.asarray(packed.idx_packed),
        jnp.asarray(packed.weighted_vals()),
        jnp.asarray(packed.lrows_packed),
        [jnp.asarray(f) for f in factors],
        num_row_blocks=packed.num_row_blocks,
        block_rows=packed.block_rows,
        tile=packed.tile,
        rank_block=rank_block,
        interpret=interpret,
    )
    return out[: packed.num_rows]


def mttkrp_packed_ref(
    packed: PackedModeLayout, factors: Sequence[jnp.ndarray]
) -> jnp.ndarray:
    """jnp oracle evaluated on the *packed* arrays (padding included) —
    bit-for-bit the same data the kernel sees (weighted values for a
    weighted packing, like ``mttkrp_packed``)."""
    idx = jnp.asarray(packed.idx_packed).T            # (G*T, W)
    vals = jnp.asarray(packed.weighted_vals())[0]
    # Reconstruct absolute relabeled rows from block-local ones.
    lrows = jnp.asarray(packed.lrows_packed)[0]
    rb = jnp.repeat(jnp.asarray(packed.rb_of), packed.tile)
    rows = lrows + rb * packed.block_rows
    out = ref_mod.mttkrp_sorted_segments(
        idx, rows, vals, [jnp.asarray(f) for f in factors],
        packed.num_row_blocks * packed.block_rows,
    )
    return out[: packed.num_rows]
