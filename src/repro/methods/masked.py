"""Masked/weighted CP for tensor completion on the shared substrate.

The COO nonzero list is reinterpreted as the OBSERVED-entry set: the
goal is ``min sum_{observed e} w_e (x_e - model_e)^2`` with everything
off the list missing (not zero) — the recommendation/imputation
workload.  The classic EM reduction keeps the whole thing on the sparse
kernels: per mode, fill the missing entries with the current model,

    Xf = model + W * (X - model),

whose MTTKRP splits into (a) the SAME spMTTKRP kernel over the observed
coordinates with per-sweep residual values ``w_e * (x_e - model_e)``,
plus (b) a closed-form rank-R dense term
``(Y_d * lambda) @ hadamard_{w != d}(gram_w)`` — then the ordinary
ridge-regularized LS solve (``ctx.solve``, shared with plain CP).  Each
mode update exactly minimizes the filled-tensor objective, which
majorizes the observed objective at the current iterate, so the observed
loss is monotone nonincreasing (EM).

Residual values change every sweep, so mode data is STRUCTURAL only
(``valued_mode_data``): the canonical->layout permutation (segment), the
canonical->slab ``val_scatter`` (pallas, computed once at pack time in
``kernels.ops``), or nothing (coo) — values are scattered on device
through ``ctx.one_mttkrp``, never repacked on host.

Per-entry weights are the USER-facing front door as well as the padding
mechanism: ``cpd_als(method="masked", weights=w)`` (and the batched /
distributed front doors) supply fractional observation confidences à la
CP-WOPT; omitted weights mean weight-1 observed entries.  Every front
door normalizes the vector by ``max(1, w.max())``
(``core.als_device.normalize_entry_weights``): the EM update is a
majorizer only for weights in [0, 1], and the weighted objective —
argmin and fit alike — is invariant under positive rescaling, so the
normalization is unobservable except that the iteration is always
stable.  The serving path appends weight-0 entries on nnz padding — a
weight-0 entry
contributes +0.0 to the residual MTTKRP and the fit, so a padded (or
down-weighted-to-zero) request is bit-equivalent to one without the
entry — the same invariance plain CP gets from zero VALUES, recovered
here from zero WEIGHTS (a zero-valued padding entry would otherwise
assert the tensor is observed-zero at the origin and bias the
completion).

Distributed execution (``core.distributed.cpd_als_distributed(
method="masked")``) runs the same EM update under ``shard_map``: every
device holds a rectangular shard of each mode layout that ALSO carries
its entries' full coordinates, values, and weights, evaluates the
residual locally at its shard's coordinates (factors are replicated),
and the partial residual MTTKRPs ``psum`` over the mesh axis; the
closed-form dense correction is computed from the replicated factors —
identical on every device — so it needs no collective, and the weighted
fit psums per-shard residual mass.  The sweep below branches on
``ctx.axis`` to pick the contract; both branches share the identical
solve tail, so sequential, batched, and distributed masked runs agree to
fp32 tolerance (pinned by ``tests/conformance``).

The fit reported is over observed entries only:
``1 - sqrt(sum w_e (x_e - model_e)^2) / sqrt(sum w_e x_e^2)``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.ref import cp_model_at_coords
from .registry import MethodSpec, register_method


def make_fit_data(tensor, entry_weights: np.ndarray | None = None):
    """(indices, values, entry_weights, weighted ||X||²).  ``entry_weights``
    default to 1 on every observed entry (the serving path appends
    weight-0 padding); a user-supplied vector carries fractional
    confidences, and the norm term weights accordingly so the reported
    fit stays scale-consistent."""
    vals = tensor.values.astype(np.float32)
    ew = (np.ones((tensor.nnz,), np.float32) if entry_weights is None
          else np.asarray(entry_weights, np.float32))
    return (
        jnp.asarray(tensor.indices),
        jnp.asarray(vals),
        jnp.asarray(ew),
        jnp.asarray(float((ew * vals) @ vals), jnp.float32),
    )


def build_sweep(ctx):
    nmodes = ctx.nmodes

    model_at = cp_model_at_coords    # one formula, shared with kernels.ref

    def solve_tail(ctx_, d, M_sp, factors, grams, weights):
        """Shared closed form + solve: identical numerics on every path."""
        V = ctx_.hadamard(grams, exclude=d)
        # Sparse residual term + closed-form dense model term =
        # MTTKRP of the EM-filled tensor (kernels.ref.
        # mttkrp_masked_residual is the reference formulation).
        M = M_sp + (factors[d] * weights[None, :]) @ V
        return ctx_.normalize(ctx_.solve(M, V))

    if ctx.axis is None:
        def sweep(state, mode_data_all, fit_data):
            factors, grams, weights = list(state[0]), list(state[1]), state[2]
            indices, values, ew, _ = fit_data
            for d in range(nmodes):
                # Fresh residual per MODE (the model moved): exact EM.
                with jax.named_scope("residual"):
                    resid = ew * (values
                                  - model_at(indices, factors, weights))
                with jax.named_scope("mttkrp"):
                    M_sp = ctx.one_mttkrp(d, mode_data_all[d], factors,
                                          resid)
                with jax.named_scope("solve"):
                    Yd, lam = solve_tail(ctx, d, M_sp, factors, grams,
                                         weights)
                factors[d] = Yd
                grams[d] = Yd.T @ Yd
                weights = lam
            with jax.named_scope("fit"):
                fit = ctx.weighted_fit(factors, weights, fit_data)
            return (tuple(factors), tuple(grams), weights), fit

        return sweep

    # Distributed (shard_map) contract: per-mode device-local shard
    # (idx_in, rows, row_perm, idx_full, vals, ew) — the residual is
    # evaluated at THIS shard's coordinates from the replicated factors,
    # the partial residual MTTKRP psums inside ctx.one_mttkrp, and the
    # dense correction is replicated-exact without a collective.
    def sweep_dist(state, mode_data_all, fit_data):
        factors, grams, weights = list(state[0]), list(state[1]), state[2]
        for d in range(nmodes):
            idx_in, rows, row_perm, idx_full, vals, ew = mode_data_all[d]
            with jax.named_scope("residual"):
                resid = ew * (vals - model_at(idx_full, factors, weights))
            with jax.named_scope("mttkrp"):
                M_sp = ctx.one_mttkrp(d, (idx_in, rows, row_perm),
                                      factors, resid)
            with jax.named_scope("solve"):
                Yd, lam = solve_tail(ctx, d, M_sp, factors, grams, weights)
            factors[d] = Yd
            grams[d] = Yd.T @ Yd
            weights = lam
        with jax.named_scope("fit"):
            fit = ctx.weighted_fit(factors, weights, fit_data)  # psums
        return (tuple(factors), tuple(grams), weights), fit

    return sweep_dist


MASKED = register_method(MethodSpec(
    name="masked",
    description="Masked/weighted CP completion (EM over observed entries): "
                "residual spMTTKRP + closed-form dense term, observed-only "
                "weighted fit; user-supplied per-entry confidences; "
                "padding is weight-0 and therefore exact.",
    build_sweep=build_sweep,
    make_fit_data=make_fit_data,
    valued_mode_data=True,
    weighted_fit=True,
))
