"""Share of the dispatched nonzero slots that were bucket padding over the
window, in % (the service's ``padding_overhead``)."""


def read(r):
    if not r.counters.get("batches"):
        return None
    return 100.0 * r.counters["padding_overhead"]
