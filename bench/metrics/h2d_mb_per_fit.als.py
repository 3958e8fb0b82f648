"""Bytes uploaded from the host per fit over the window, in MB: the
``h2d_bytes`` that the program's ledger (``repro.obs.ledger``) counted
since the window opened, over the fits of the window.  A program whose
ledger keeps no such count gives nothing."""


def read(r):
    from repro.obs.ledger import LEDGER

    counts = getattr(LEDGER, "counts", None)
    fits = r.counters.get("fits", 0)
    if counts is None or not fits:
        return None
    return counts()["h2d_bytes"] / 1e6 / fits
