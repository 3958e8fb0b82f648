"""Executables traced inside the window, from the program's retrace
ledger (``repro.obs.ledger``), reset when the window opens.  It should be 0."""


def read(r):
    n = r.counters.get("compiles")
    return None if n is None else float(n)
