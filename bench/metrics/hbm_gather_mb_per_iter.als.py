"""Factor rows the MTTKRP gathered in HBM per ALS iteration, in MB: the
``hbm_gather_bytes`` that the program's ledger (``repro.obs.ledger``)
counted for the fused sweeps since the window opened, over the iterations
of the window.  These are logical float32 bytes, before lane padding; 0
where no factor was tall enough to be gathered in HBM.  A program that
does not count them (no ``repro.kernels.ops.hbm_gather_bytes``) gives
nothing."""


def read(r):
    from repro.kernels import ops
    from repro.obs.ledger import LEDGER

    counts = getattr(LEDGER, "counts", None)
    iters = r.counters.get("iters", 0)
    if counts is None or not hasattr(ops, "hbm_gather_bytes") or not iters:
        return None
    return counts("sweep_block").get("hbm_gather_bytes", 0) / 1e6 / iters
