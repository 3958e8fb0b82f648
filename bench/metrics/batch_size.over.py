"""Requests per dispatched batch over the window: the service's completed
requests over its batches."""


def read(r):
    n = r.counters.get("batches", 0)
    return r.counters["completed"] / n if n else None
