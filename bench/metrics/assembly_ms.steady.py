"""Host-side batch assembly per dispatch, in ms, over the window: the
service's ``dispatch.assembly_s`` over its dispatch count
(``repro.serve.batched_engine.prepare_batch``)."""


def read(r):
    n = r.counters.get("dispatches", 0)
    return 1e3 * r.counters["assembly_s"] / n if n else None
