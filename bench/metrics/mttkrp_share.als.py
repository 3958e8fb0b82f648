"""Device time under the ``mttkrp`` scope as a share of device busy time, in %."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0.0:
        return None
    return 100.0 * r.trace.scope_s.get("mttkrp", 0.0) / r.trace.busy_s
