"""Plain CP-ALS: the reference that decides whether a run is ``correct``.

It is written from the algorithm and imports nothing of the program.  For
each mode ``d`` in turn: the MTTKRP ``M = X_(d) (KRP of the other
factors)``, the Hadamard product ``V`` of the other factors' grams, the
ridge solve ``Y = M (V + ridge I)^-1`` with ``ridge = 1e-10 * max(tr V /
R, 1)``, column norms ``lam`` (a column of norm at most 1e-12 keeps norm
1) and ``Y / lam``.  After the sweep the fit is ``1 - ||X - model|| /
||X||``, with ``||X - model||^2 = ||X||^2 - 2 <X, model> + ||model||^2``
taken sparsely.  A fit starts from the seeded initial factors that the
service's API promises for a seed: for each mode in order,
``numpy.random.default_rng(seed).standard_normal((I_d, R))`` as float32.

Two places compute the sparse parts (the MTTKRP and ``<X, model>``); the
small dense parts (grams, solve, norms) are float64 numpy on the host in
both:

* ``HostKernels``: float64 numpy over the COO list, for the service's
  small tensors;
* ``DeviceKernels``: float32 ``jax.numpy`` over the COO list, on the
  accelerator, for a FROSTT-scale tensor whose float64 host reference
  would outlast the measured window.  Matmul precision is ``highest``.

``precision="bfloat16"`` rounds the tensor's values and every factor to
bfloat16 before the sparse parts: the precision control, which the
comparison must refuse.  The rounding is done on the host: a float32 to
bfloat16 to float32 round trip inside an XLA program may be folded away
(XLA allows excess precision by default), and on a TPU it was.
"""
from __future__ import annotations

import numpy as np

PRECISIONS = ("float32", "bfloat16")


def init_factors(shape, rank: int, seed: int) -> list[np.ndarray]:
    """The seeded initial factors of a fit (module docstring)."""
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((int(s), rank)).astype(np.float32)
            for s in shape]


def _to_bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float32)


class HostKernels:
    """Float64 numpy MTTKRP and inner product over a COO list."""

    def __init__(self, indices: np.ndarray, values: np.ndarray, shape,
                 precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.shape = tuple(int(s) for s in shape)
        self.indices = np.asarray(indices)
        vals = np.asarray(values, np.float32)
        if precision == "bfloat16":
            vals = _to_bf16(vals)
        self.values = vals.astype(np.float64)
        # Per mode: the nonzeros sorted by their row, and each row's run.
        self._order, self._starts, self._rows = [], [], []
        for d in range(len(self.shape)):
            order = np.argsort(self.indices[:, d], kind="stable")
            rows = self.indices[order, d]
            starts = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
            self._order.append(order)
            self._starts.append(starts)
            self._rows.append(rows[starts])

    def _factor(self, f: np.ndarray) -> np.ndarray:
        if self.precision == "bfloat16":
            return _to_bf16(f.astype(np.float32)).astype(np.float64)
        return np.asarray(f, np.float64)

    def mttkrp(self, factors, mode: int) -> np.ndarray:
        order = self._order[mode]
        idx = self.indices[order]
        acc = self.values[order, None].copy()
        for w, f in enumerate(factors):
            if w != mode:
                acc = acc * self._factor(f)[idx[:, w]]
        out = np.zeros((self.shape[mode], acc.shape[1]))
        if len(order):
            out[self._rows[mode]] = np.add.reduceat(acc, self._starts[mode],
                                                    axis=0)
        return out

    def innerprod(self, factors, weights) -> float:
        acc = np.ones((len(self.values), len(weights)))
        for w, f in enumerate(factors):
            acc = acc * self._factor(f)[self.indices[:, w]]
        return float(self.values @ (acc @ np.asarray(weights, np.float64)))


class DeviceKernels:
    """Float32 ``jax.numpy`` MTTKRP and inner product on the accelerator."""

    def __init__(self, indices: np.ndarray, values: np.ndarray, shape,
                 precision: str = "float32"):
        import jax
        import jax.numpy as jnp

        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.shape = tuple(int(s) for s in shape)
        nmodes = len(self.shape)

        def mttkrp(idx, vals, factors, mode):
            acc = vals[:, None]
            for w in range(nmodes):
                if w != mode:
                    acc = acc * jnp.take(factors[w], idx[:, w], axis=0)
            return jax.ops.segment_sum(acc, idx[:, mode],
                                       num_segments=self.shape[mode],
                                       indices_are_sorted=True)

        def innerprod(idx, vals, factors, weights):
            acc = jnp.ones((vals.shape[0], weights.shape[0]), jnp.float32)
            for w in range(nmodes):
                acc = acc * jnp.take(factors[w], idx[:, w], axis=0)
            with jax.default_matmul_precision("highest"):
                return vals @ (acc @ weights)

        self._mttkrp = jax.jit(mttkrp, static_argnums=(3,))
        self._innerprod = jax.jit(innerprod)
        idx = jnp.asarray(np.asarray(indices, np.int32))
        self._vals = jnp.asarray(self._host(values))
        self._idx = idx
        # Per mode, the COO list sorted by that mode's row.
        self._sorted = []
        for d in range(nmodes):
            order = jnp.argsort(idx[:, d], stable=True)
            self._sorted.append((idx[order], self._vals[order]))
        self._jnp = jnp

    def _host(self, a) -> np.ndarray:
        a = np.asarray(a, np.float32)
        return _to_bf16(a) if self.precision == "bfloat16" else a

    def mttkrp(self, factors, mode: int) -> np.ndarray:
        jnp = self._jnp
        dev = [jnp.asarray(self._host(f)) for f in factors]
        idx, vals = self._sorted[mode]
        return np.asarray(self._mttkrp(idx, vals, dev, mode), np.float64)

    def innerprod(self, factors, weights) -> float:
        jnp = self._jnp
        dev = [jnp.asarray(self._host(f)) for f in factors]
        w = jnp.asarray(np.asarray(weights, np.float32))
        return float(self._innerprod(self._idx, self._vals, dev, w))


def model_at(factors, weights, indices: np.ndarray) -> np.ndarray:
    """The CP model ``sum_r w_r prod_d F_d[i_d, r]`` at COO coordinates, in
    float64."""
    acc = np.ones((len(indices), len(weights)))
    for d, f in enumerate(factors):
        acc = acc * np.asarray(f, np.float64)[indices[:, d]]
    return acc @ np.asarray(weights, np.float64)


def cp_als(kernels, norm_x_sq: float, rank: int, n_iters: int, seed: int):
    """``(fits, factors, weights)``: the fit after each of ``n_iters`` sweeps
    of plain CP-ALS, and the final column-normalized factors and weights."""
    shape = kernels.shape
    factors = [f.astype(np.float64) for f in init_factors(shape, rank, seed)]
    grams = [f.T @ f for f in factors]
    weights = np.ones(rank)
    norm_x = np.sqrt(norm_x_sq)
    fits = []
    for _ in range(n_iters):
        for d in range(len(shape)):
            m = kernels.mttkrp(factors, d)
            v = np.ones((rank, rank))
            for w, g in enumerate(grams):
                if w != d:
                    v = v * g
            ridge = 1e-10 * max(np.trace(v) / rank, 1.0)
            y = np.linalg.solve(v + ridge * np.eye(rank), m.T).T
            lam = np.linalg.norm(y, axis=0)
            lam = np.where(lam > 1e-12, lam, 1.0)
            factors[d] = y / lam
            grams[d] = factors[d].T @ factors[d]
            weights = lam
        ip = kernels.innerprod(factors, weights)
        v = np.ones((rank, rank))
        for g in grams:
            v = v * g
        model_sq = float(weights @ v @ weights)
        resid_sq = max(norm_x_sq - 2.0 * ip + model_sq, 0.0)
        fits.append(1.0 - np.sqrt(resid_sq) / max(norm_x, 1e-12))
    return fits, factors, weights
