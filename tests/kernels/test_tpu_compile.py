"""Compile (not run) the Pallas MTTKRP kernel for a described TPU v5e.

The TPU compiler refuses things interpret mode accepts (unaligned blocks,
gathers Mosaic cannot lower, more VMEM than the chip has), so the kernel
is compiled here at the widths the main path uses: chicago at its full
FROSTT size under the tiling ``core.plan`` picks, the cpd_als default
tiling with its 6,186-row factor gathered in HBM, the vmapped serving
kernel, and a rank-tiled kernel.  The topology is described inside a fixture, never
while a module is imported.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core import plan_bucket, quantize_nnz
from repro.kernels import ops as kops
from repro.kernels.mttkrp_pallas import mttkrp_pallas

CHICAGO = (6186, 24, 77, 32)
CHICAGO_NNZ = 5_330_673


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, *, G, tile, block_rows, num_row_blocks, rank,
             rank_block, factor_rows, batch=None):
    """Lower and compile the kernel for shapes on the described chip;
    returns the compiled HLO text."""
    W = len(factor_rows)

    def kernel(rb_of, first, idx, vals, lrows, *facs):
        return mttkrp_pallas(rb_of, first, idx, vals, lrows, list(facs),
                             num_row_blocks=num_row_blocks,
                             block_rows=block_rows, tile=tile,
                             rank_block=rank_block, interpret=False)

    shapes = ([((G,), jnp.int32), ((G,), jnp.int32),
               ((W, G * tile), jnp.int32), ((1, G * tile), jnp.float32),
               ((1, G * tile), jnp.int32)]
              + [((n, rank), jnp.float32) for n in factor_rows])
    lead = () if batch is None else (batch,)
    args = [jax.ShapeDtypeStruct(lead + s, dt, sharding=one_chip)
            for s, dt in shapes]
    fn = kernel if batch is None else jax.vmap(kernel)
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _hbm_gather_before_kernel(text: str) -> bool:
    """Whether the compiled HLO holds a gather of the ``hbm_gather`` scope,
    and it comes before the kernel's custom call."""
    gathers = [m.start() for m in re.finditer(r"\bgather\(", text)
               if "hbm_gather" in text[m.start():text.find("\n", m.start())]]
    return bool(gathers) and gathers[0] < text.index("tpu_custom_call")


@pytest.mark.parametrize("mode", range(len(CHICAGO)))
def test_chicago_full_size_planned_tiling(one_chip, mode):
    """Every mode of chicago at full size, R=32, under core.plan's
    (block_rows, tile, rank_block) and its static slab cap.  Modes 1-3
    gather the 6,186-row factor in HBM ahead of the kernel; mode 0's
    factors are all short, so it gathers nothing there."""
    mp = plan_bucket(CHICAGO, quantize_nnz(CHICAGO_NNZ), 32).modes[mode]
    text = _compile(one_chip, G=mp.slab_cap, tile=mp.tile,
                    block_rows=mp.block_rows,
                    num_row_blocks=mp.num_row_blocks, rank=32,
                    rank_block=mp.rank_block,
                    factor_rows=[n for w, n in enumerate(CHICAGO)
                                 if w != mode])
    assert "tpu_custom_call" in text
    assert _hbm_gather_before_kernel(text) == (mode != 0)
    assert ("hbm_gather" in text) == (mode != 0)


def test_gather_of_6186_row_factor(one_chip):
    """Mode 1 under the default tiling cpd_als(backend="pallas") packs
    with: the 6,186-row factor is gathered in HBM and streamed, the other
    two by one-hot matmuls in the kernel."""
    br, tile = kops.DEFAULT_BLOCK_ROWS, kops.DEFAULT_TILE
    frows = [6186, 77, 32]
    rb = kops.auto_rank_block(32, br, tile, frows, mode=1)
    text = _compile(one_chip, G=1 + CHICAGO_NNZ // tile, tile=tile,
                    block_rows=br, num_row_blocks=1, rank=32,
                    rank_block=rb, factor_rows=frows)
    assert "tpu_custom_call" in text
    assert _hbm_gather_before_kernel(text)


def test_vmapped_kernel_batch_8(one_chip):
    """The serving path's kernel: jax.vmap over B=8 bucket-mates of the
    chicago-like stream shape."""
    shape, cap, rank = (128, 24, 77, 32), quantize_nnz(500), 8
    mp = plan_bucket(shape, cap, rank).modes[0]
    text = _compile(one_chip, G=mp.slab_cap, tile=mp.tile,
                    block_rows=mp.block_rows,
                    num_row_blocks=mp.num_row_blocks, rank=rank,
                    rank_block=mp.rank_block, factor_rows=list(shape[1:]),
                    batch=8)
    assert "tpu_custom_call" in text


def test_rank_tiled_kernel(one_chip):
    """R=256 in two 128-column blocks compiles, with one resident factor
    and one streamed from its HBM gather.  The planner keeps the whole
    rank for these factors: the tall one holds one (tile, R) block in
    VMEM, not its 20,000 rows."""
    frows = [500, 20_000]
    assert kops.auto_rank_block(256, 128, 256, frows, mode=0) == 256
    text = _compile(one_chip, G=64, tile=256, block_rows=128,
                    num_row_blocks=8, rank=256, rank_block=128,
                    factor_rows=frows)
    assert "tpu_custom_call" in text
    assert _hbm_gather_before_kernel(text)
