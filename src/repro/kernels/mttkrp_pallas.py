"""Pallas TPU kernel for sorted segmented spMTTKRP.

TPU-native re-think of the paper's R x P thread-block kernel (§IV-B):

  * The mode-specific layout pre-sorts nonzeros by (relabeled) output row,
    so the scatter-update becomes a *segmented reduction* — no atomics
    (TPU has none; the paper's Local_Update/Global_Update dichotomy moves
    to the partitioning level, see core/distributed.py).
  * Nonzeros are packed into fixed ``tile``-sized slabs grouped under
    ``block_rows``-sized output row blocks (see ops.pack_slabs).  Grid =
    one step per slab; consecutive slabs of the same row block revisit the
    same output block, which therefore stays resident in VMEM and is only
    written back to HBM once per row block — this is the paper's
    "eliminate intermediate-value traffic" property, realized through the
    Pallas pipeline instead of L1 atomics.
  * Rank is tiled: the grid is 2-D ``(R_blocks, G)`` with the slab
    dimension minor, so each rank block makes one full pass over the
    slabs while only ``rank_block`` factor/output columns are resident in
    VMEM.  Columns are independent in MTTKRP, so rank tiling is exact
    (bit-identical to the single-block kernel) and removes the hard VMEM
    rank ceiling the single-block version had.
  * Factor-row gathers and the final scatter-reduce both become one-hot
    matmuls on the MXU.  A factor taller than ``GATHER_CHUNK`` rows is
    zero-padded to a chunk multiple and gathered chunk by chunk in a
    ``fori_loop``: each nonzero's one-hot row has a single 1 across all
    chunks, so the sum over chunks is the exact gathered row.  Mosaic has
    no vector gather from a VMEM ref, so this is the form that compiles
    for any factor whose block fits VMEM.  Matmuls run at f32 contract
    precision, which makes the one-hot gather exact.  The Hadamard
    accumulator ``l`` (paper's l(r)) lives in VREGs/VMEM for its whole
    life.

Block layout (VMEM, per grid step):
  idx_ref   : (W, T)   int32   input-mode indices (lane dim = T)
  val_ref   : (1, T)   float   nonzero values
  lrow_ref  : (1, T)   int32   output row local to this row block
  factors   : (I_w, RB) each   one rank block of each factor matrix
  out_ref   : (BR, RB) float32 one (row block, rank block) output tile,
                               revisited across slabs of the row block

Scalar-prefetch:
  rb_of (G,) int32  output row-block id per grid step (drives out index_map)
  first (G,) int32  1 on the first slab of each row block (zero-init gate)
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Factor rows gathered per one-hot matmul: a lane multiple, so the
# (tile, GATHER_CHUNK) one-hot operand is MXU-aligned and stays small in
# VMEM however tall the factor is.
GATHER_CHUNK = 512

# Scoped VMEM the kernel may use.  A v5e TensorCore has 128 MiB of VMEM;
# the planner (kernels.ops) sizes blocks against a budget below this.
VMEM_LIMIT_BYTES = 64 * 2**20

# The one-hot gather is exact only at f32 contract precision, so the
# kernel asks for it.  On a v5e, Mosaic gives f32 operands that precision
# at DEFAULT too: both settings produce bit-identical MTTKRPs there.
_F32 = lax.Precision.HIGHEST


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: interpret the kernel only on the CPU
    backend, compile it everywhere else.  Every front door defaults to
    None and resolves here, so a TPU run never interprets silently."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _gather_rows(fac, idx_col, tile: int):
    """``fac[idx]`` as one-hot matmuls: (tile, RB) f32.  ``idx_col`` is
    the (tile, 1) int32 index column; ``fac`` a VMEM ref whose row count
    is at most ``GATHER_CHUNK`` or a multiple of it."""
    rows, width = fac.shape
    if rows <= GATHER_CHUNK:
        iota = lax.broadcasted_iota(jnp.int32, (tile, rows), 1)
        onehot = (idx_col == iota).astype(jnp.float32)
        return jnp.dot(onehot, fac[...].astype(jnp.float32),
                       precision=_F32, preferred_element_type=jnp.float32)
    iota = lax.broadcasted_iota(jnp.int32, (tile, GATHER_CHUNK), 1)

    def chunk(c, acc):
        start = pl.multiple_of(c * GATHER_CHUNK, GATHER_CHUNK)
        onehot = (idx_col == iota + start).astype(jnp.float32)
        part = fac[pl.ds(start, GATHER_CHUNK), :].astype(jnp.float32)
        return acc + jnp.dot(onehot, part, precision=_F32,
                             preferred_element_type=jnp.float32)

    return lax.fori_loop(0, rows // GATHER_CHUNK, chunk,
                         jnp.zeros((tile, width), jnp.float32))


def _kernel(
    rb_of_ref,
    first_ref,
    idx_ref,
    val_ref,
    lrow_ref,
    *refs,
    num_inputs: int,
    block_rows: int,
    tile: int,
):
    factor_refs = refs[:num_inputs]
    out_ref = refs[num_inputs]
    g = pl.program_id(1)          # slab index (minor grid dimension)

    @pl.when(first_ref[g] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = val_ref[0, :].astype(jnp.float32)          # (T,)
    prod = vals[:, None]                              # (T, 1) -> bcast to (T, RB)
    for w in range(num_inputs):
        idx_col = idx_ref[w, :][:, None]              # (T, 1)
        prod = prod * _gather_rows(factor_refs[w], idx_col, tile)

    # Segmented reduce into the row block: one-hot^T @ prod on the MXU.
    lrow = lrow_ref[0, :]                             # (T,)
    iota_r = lax.broadcasted_iota(jnp.int32, (tile, block_rows), 1)
    scatter = (lrow[:, None] == iota_r).astype(jnp.float32)   # (T, BR)
    out_ref[...] += jnp.dot(
        scatter.T, prod, precision=_F32, preferred_element_type=jnp.float32
    )


def mttkrp_pallas(
    rb_of: jnp.ndarray,          # (G,) int32
    first: jnp.ndarray,          # (G,) int32
    idx_packed: jnp.ndarray,     # (W, G*T) int32
    vals_packed: jnp.ndarray,    # (1, G*T) float
    lrows_packed: jnp.ndarray,   # (1, G*T) int32
    factors: Sequence[jnp.ndarray],  # W arrays (I_w, R)
    *,
    num_row_blocks: int,
    block_rows: int,
    tile: int,
    rank_block: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Run the segmented MTTKRP kernel. Returns (num_row_blocks*block_rows, R) f32.

    ``rank_block`` tiles the rank dimension: each rank block re-streams the
    slabs with only that block of factor/output columns in VMEM.  ``None``
    (or >= R) keeps the whole rank resident — the original behavior.  A
    compiled kernel needs ``rank_block`` equal to R or a multiple of 128
    (the planner in ``kernels.ops`` only offers those).
    ``interpret=None`` interprets on the CPU backend only (see
    ``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    W = idx_packed.shape[0]
    if W != len(factors):
        raise ValueError(f"{W} index rows but {len(factors)} input factors")
    G = rb_of.shape[0]
    if idx_packed.shape[1] != G * tile:
        raise ValueError("packed arrays must have G*tile columns")
    R = factors[0].shape[1]
    if rank_block is None or rank_block >= R:
        rank_block = R
    if rank_block < 1:
        raise ValueError(f"rank_block must be >= 1, got {rank_block}")
    num_rank_blocks = -(-R // rank_block)
    R_pad = num_rank_blocks * rank_block
    # Zero-pad the rank dimension so it divides evenly (padded columns
    # compute zeros and are sliced off below), and tall factors' rows to
    # a GATHER_CHUNK multiple (padded rows are never selected).
    padded = []
    for f in factors:
        rows = f.shape[0]
        rows_pad = (rows if rows <= GATHER_CHUNK
                    else -(-rows // GATHER_CHUNK) * GATHER_CHUNK)
        if rows_pad != rows or R_pad != R:
            f = jnp.pad(f, ((0, rows_pad - rows), (0, R_pad - R)))
        padded.append(f)
    factors = padded

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_rank_blocks, G),
        in_specs=[
            pl.BlockSpec((W, tile), lambda r, g, rb, fi: (0, g)),
            pl.BlockSpec((1, tile), lambda r, g, rb, fi: (0, g)),
            pl.BlockSpec((1, tile), lambda r, g, rb, fi: (0, g)),
        ]
        + [
            pl.BlockSpec((f.shape[0], rank_block), lambda r, g, rb, fi: (0, r))
            for f in factors
        ],
        out_specs=pl.BlockSpec(
            (block_rows, rank_block), lambda r, g, rb, fi: (rb[g], r)
        ),
    )
    kernel = functools.partial(
        _kernel,
        num_inputs=W,
        block_rows=block_rows,
        tile=tile,
    )
    out_shape = jax.ShapeDtypeStruct(
        (num_row_blocks * block_rows, R_pad), jnp.float32
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="mttkrp_pallas",     # the kernel's name in a device trace
    )(rb_of, first, idx_packed, vals_packed, lrows_packed, *factors)
    if R_pad != R:
        out = out[:, :R]
    return out
