"""Device-to-host synchronizations per ALS iteration, summed over the fits
of the window (``CPDResult.host_syncs``)."""


def read(r):
    iters = r.counters.get("iters", 0)
    return r.counters["host_syncs"] / iters if iters else None
