"""Quickstart: decompose a small sparse tensor with CPD-ALS.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import cpd_als, low_rank_sparse, make_plan, mttkrp, random_sparse
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. a synthetic 3-mode sparse tensor (power-law index skew, like FROSTT)
t = random_sparse((500, 120, 40), 20_000, seed=0, distribution="powerlaw")
print(f"tensor {t.shape}, nnz={t.nnz}, density={t.density:.2e}")

# 2. the paper's preprocessing: one mode-specific layout per mode,
#    adaptive load balancing across kappa partitions
plan = make_plan(t, kappa=82)
for d, lay in enumerate(plan.layouts):
    print(f"  mode {d}: scheme={lay.scheme.name} "
          f"(I_d={t.shape[d]}, partitions={lay.kappa})")

# 3. one MTTKRP along mode 0 (the bottleneck kernel)
R = 16
rng = np.random.default_rng(0)
factors = [np.random.default_rng(d).standard_normal((I, R)).astype(np.float32)
           for d, I in enumerate(t.shape)]
M = mttkrp(plan, factors, mode=0)
print(f"MTTKRP mode 0 -> {M.shape}")

# 4. full CPD-ALS — the default engine is the device-resident fused sweep:
#    MTTKRP, gram updates, solve, normalization, and the sparse fit run as
#    ONE jitted computation; the host syncs only at the convergence check.
res = cpd_als(t, rank=R, plan=plan, n_iters=10, check_every=2, verbose=True)
print(f"final fit {res.fits[-1]:.4f} in {res.iters} iters "
      f"[{res.engine} engine, {res.host_syncs} host syncs] "
      f"in {res.total_seconds:.2f}s")

# 5. the original per-mode host loop survives for comparison
res_h = cpd_als(t, rank=R, plan=plan, n_iters=10, engine="host")
print(f"host engine: {res_h.host_syncs} host syncs, "
      f"MTTKRP time {res_h.mttkrp_seconds:.2f}s of {res_h.total_seconds:.2f}s")
