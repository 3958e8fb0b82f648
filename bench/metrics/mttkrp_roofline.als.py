"""MTTKRP share of its roofline, in %: the least time the chip needs for
the MTTKRP work run inside the traced window (``bench.work``), over the
device time of the operations under the ``mttkrp`` scope there."""
from bench.work import least_time


def read(r):
    t = r.trace.scope_s.get("mttkrp", 0.0) if r.trace else 0.0
    if t <= 0.0 or r.traced_work is None or r.traced_work.operations <= 0:
        return None
    return 100.0 * least_time(r.traced_work, r.peaks)[0] / t
