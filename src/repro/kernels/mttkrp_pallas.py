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
  * Factor-row gathers adapt to the factor's height.  A factor of at
    most ``GATHER_CHUNK`` rows stays resident in VMEM and is gathered in
    the kernel by a one-hot matmul on the MXU (the paper's
    intermediate-free path; Mosaic has no vector gather from a VMEM ref).
    A taller factor is gathered by XLA in HBM before the kernel, in packed
    slot order, and its rows stream in beside the slab like the values:
    a one-hot matmul's work grows with the factor's height, an HBM gather's
    does not.  Both return the factor's rows exactly (matmuls run at f32
    contract precision), and the Hadamard product multiplies the inputs in
    the same order either way.  The final scatter-reduce is a one-hot
    matmul too.  The Hadamard accumulator ``l`` (paper's l(r)) lives in
    VREGs/VMEM for its whole life.

Block layout (VMEM, per grid step):
  idx_ref   : (W, T)   int32   input-mode indices (lane dim = T)
  val_ref   : (1, T)   float   nonzero values
  lrow_ref  : (1, T)   int32   output row local to this row block
  factors   : (I_w, RB)        one rank block of a factor of <= GATHER_CHUNK
                               rows (resident), or
              (T, RB)          its gathered rows for this slab (taller ones)
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

from ..obs.ledger import LEDGER


# The tallest factor the kernel gathers by a one-hot matmul from VMEM; a
# taller one is gathered in HBM before the kernel.  A lane multiple, so the
# (tile, rows) one-hot operand stays within a few MXU passes.
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
    """``fac[idx]`` as a one-hot matmul: (tile, RB) f32.  ``idx_col`` is
    the (tile, 1) int32 index column; ``fac`` a VMEM ref of at most
    ``GATHER_CHUNK`` rows."""
    iota = lax.broadcasted_iota(jnp.int32, (tile, fac.shape[0]), 1)
    onehot = (idx_col == iota).astype(jnp.float32)
    return jnp.dot(onehot, fac[...].astype(jnp.float32),
                   precision=_F32, preferred_element_type=jnp.float32)


def _kernel(
    rb_of_ref,
    first_ref,
    idx_ref,
    val_ref,
    lrow_ref,
    *refs,
    gathered: tuple[bool, ...],
    block_rows: int,
    tile: int,
):
    num_inputs = len(gathered)
    factor_refs = refs[:num_inputs]
    out_ref = refs[num_inputs]
    g = pl.program_id(1)          # slab index (minor grid dimension)

    @pl.when(first_ref[g] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = val_ref[0, :].astype(jnp.float32)          # (T,)
    prod = vals[:, None]                              # (T, 1) -> bcast to (T, RB)
    for w in range(num_inputs):
        if gathered[w]:                               # rows gathered in HBM
            rows = factor_refs[w][...].astype(jnp.float32)
        else:
            idx_col = idx_ref[w, :][:, None]          # (T, 1)
            rows = _gather_rows(factor_refs[w], idx_col, tile)
        prod = prod * rows

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

    An input factor of more than ``GATHER_CHUNK`` rows is gathered here,
    in HBM, under ``jax.named_scope("hbm_gather")``: ``(G*T, R)`` rows in
    packed slot order (padding slots carry value 0 and gather row 0), which
    the kernel streams a ``(tile, rank_block)`` block at a time.  Each
    trace counts how many inputs went each way under the ledger kind
    ``pallas_gather`` (``hbm``, ``onehot``).
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
    # compute zeros and are sliced off below).
    if R_pad != R:
        factors = [jnp.pad(f, ((0, 0), (0, R_pad - R))) for f in factors]
    gathered = tuple(f.shape[0] > GATHER_CHUNK for f in factors)
    LEDGER.count("pallas_gather", hbm=sum(gathered),
                 onehot=W - sum(gathered))
    operands, factor_specs = [], []
    for w, f in enumerate(factors):
        if gathered[w]:
            with jax.named_scope("hbm_gather"):
                operands.append(jnp.take(f, idx_packed[w], axis=0,
                                         mode="clip"))
            factor_specs.append(pl.BlockSpec(
                (tile, rank_block), lambda r, g, rb, fi: (g, r)))
        else:
            operands.append(f)
            factor_specs.append(pl.BlockSpec(
                (f.shape[0], rank_block), lambda r, g, rb, fi: (0, r)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_rank_blocks, G),
        in_specs=[
            pl.BlockSpec((W, tile), lambda r, g, rb, fi: (0, g)),
            pl.BlockSpec((1, tile), lambda r, g, rb, fi: (0, g)),
            pl.BlockSpec((1, tile), lambda r, g, rb, fi: (0, g)),
        ]
        + factor_specs,
        out_specs=pl.BlockSpec(
            (block_rows, rank_block), lambda r, g, rb, fi: (rb[g], r)
        ),
    )
    kernel = functools.partial(
        _kernel,
        gathered=gathered,
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
    )(rb_of, first, idx_packed, vals_packed, lrows_packed, *operands)
    if R_pad != R:
        out = out[:, :R]
    return out
