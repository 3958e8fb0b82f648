"""Seeded synthetic sparse tensors: the benchmark's own copy of the generator.

This follows ``repro.core.coo.random_sparse(distribution="powerlaw")``:
nonzeros are drawn with per-mode power-law skew (the r-th hottest index of
a mode with more than two rows has weight ``(r + 1) ** -0.5``; modes of at
most two rows are uniform), duplicate coordinates are dropped by first
occurrence in draw order, draws continue until ``nnz`` distinct
coordinates exist, values are standard normal float32 with magnitudes
below 1e-3 raised to 1e-3, and the result is sorted in row-major (COO
canonical) order.  It is vectorised: one inverse-CDF draw per mode and one
sort of linear keys per round, instead of a categorical sampler and a
lexsort, which makes FROSTT chicago several times faster to build.

The copy exists so that the benchmark's traffic does not move when the
program's generator changes.  A tensor is returned as a plain
``(indices int32 (nnz, N), values float32 (nnz,), shape)`` triple; the
harness wraps it in the program's container at the boundary.
"""
from __future__ import annotations

import numpy as np

# Draw a little more than the deficit: duplicates are dropped afterwards.
_OVERSAMPLE = 1.15
_MAX_ROUNDS = 32


def _mode_cdf(size: int) -> np.ndarray | None:
    """CDF of the power-law weights of one mode; None means uniform."""
    if size <= 2:
        return None
    w = (np.arange(size, dtype=np.float64) + 1.0) ** -0.5
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def _draw_keys(rng: np.random.Generator, shape, cdfs, m: int) -> np.ndarray:
    """``m`` row-major linear keys with the per-mode skew."""
    key = np.zeros(m, dtype=np.int64)
    for size, cdf in zip(shape, cdfs):
        if cdf is None:
            col = rng.integers(0, size, size=m, dtype=np.int64)
        else:
            col = np.searchsorted(cdf, rng.random(m), side="right")
            np.minimum(col, size - 1, out=col)
        key *= size
        key += col
    return key


def powerlaw_sparse(shape, nnz: int, seed: int):
    """``nnz`` distinct power-law-skewed coordinates of ``shape`` with
    standard normal values, from ``seed``; see the module docstring."""
    shape = tuple(int(s) for s in shape)
    cells = float(np.prod([float(s) for s in shape]))
    if nnz > cells:
        raise ValueError(f"{nnz} nonzeros do not fit in shape {shape}")
    rng = np.random.default_rng(seed)
    cdfs = [_mode_cdf(s) for s in shape]
    drawn = np.empty(0, dtype=np.int64)
    have = 0
    for _ in range(_MAX_ROUNDS):
        # Size the next draw by the share of draws that were new so far.
        new_share = have / len(drawn) if len(drawn) else 1.0
        m = int((nnz - have) / max(new_share, 0.05) * _OVERSAMPLE) + 64
        drawn = np.concatenate([drawn, _draw_keys(rng, shape, cdfs, m)])
        _, first = np.unique(drawn, return_index=True)
        have = len(first)
        if have >= nnz:
            break
    else:
        raise RuntimeError(f"could not draw {nnz} distinct coordinates of "
                           f"{shape} in {_MAX_ROUNDS} rounds")
    first.sort()                      # first occurrences, in draw order
    keys = np.sort(drawn[first[:nnz]])
    idx = np.empty((nnz, len(shape)), dtype=np.int32)
    rest = keys
    for d in reversed(range(len(shape))):
        rest, idx[:, d] = np.divmod(rest, shape[d])
    vals = rng.standard_normal(nnz).astype(np.float32)
    vals = np.where(np.abs(vals) < 1e-3, np.float32(1e-3), vals)
    return idx, vals.astype(np.float32), shape
