"""One device scope per mode: the lowered sweep block's op metadata names
``mttkrp/mode<d>`` for every mode, on the segment backend and on the
Pallas kernel (interpret mode on the CPU)."""
import re

import jax.numpy as jnp
import pytest

from repro.core import als_device, make_plan, random_sparse
from repro.core.mttkrp import collect_mode_data

SHAPE, RANK = (10, 8, 6, 5), 4


@pytest.mark.parametrize("backend", ["segment", "pallas"])
def test_sweep_block_scopes_each_mode(backend):
    t = random_sparse(SHAPE, 300, seed=11)
    plan = make_plan(t, 1)
    mode_data, meta = collect_mode_data(plan, backend, RANK)
    state = als_device.init_state(t.shape, RANK, 0)
    fit_data = (jnp.asarray(t.indices), jnp.asarray(t.values, jnp.float32),
                jnp.asarray(1.0, jnp.float32))
    block = als_device._build_sweep_block(
        backend, len(SHAPE), RANK, SHAPE, meta, True, False, "inv", 2, "cp")
    text = block.lower(state, mode_data, fit_data).as_text(debug_info=True)
    scoped = set(re.findall(r"mttkrp/mode(\d+)/", text))
    assert scoped == {str(d) for d in range(len(SHAPE))}
    # Every MTTKRP operation sits under one mode's scope.
    assert not re.search(r"/mttkrp/(?!mode\d+/)", text)
    if backend == "pallas":
        assert {f"mttkrp/mode{d}/mttkrp_pallas" for d in range(len(SHAPE))
                } <= set(re.findall(r"mttkrp/mode\d+/mttkrp_pallas", text))

