"""CPD of a FROSTT-like tensor, comparing execution engines + schemes.

    PYTHONPATH=src python examples/decompose_tensor.py [dataset] [--pallas] [--host]

``--host`` uses the original per-mode host loop; the default is the fused
device-resident engine (one jitted sweep per iteration).
"""
import sys
import time

from repro.core import Scheme, cpd_als, frostt_like, make_plan
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

name = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith("-") \
    else "chicago"
use_pallas = "--pallas" in sys.argv
engine = "host" if "--host" in sys.argv else "fused"
t = frostt_like(name, scale=0.01, seed=0)
print(f"{name}: shape={t.shape} nnz={t.nnz} engine={engine}")

for label, scheme in [("adaptive", None),
                      ("scheme-1 only", Scheme.INDEX_PARTITION),
                      ("scheme-2 only", Scheme.NNZ_PARTITION)]:
    plan = make_plan(t, kappa=82, scheme=scheme)
    backend = "pallas" if use_pallas else "segment"
    t0 = time.perf_counter()
    res = cpd_als(t, rank=32, plan=plan, n_iters=3, backend=backend,
                  engine=engine, check_every=3, tol=-1.0)
    wall = time.perf_counter() - t0
    print(f"  {label:14s} [{backend}/{res.engine}]: fit={res.fits[-1]:.4f} "
          f"wall={wall:.3f}s syncs={res.host_syncs}")
