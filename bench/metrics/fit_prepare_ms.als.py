"""Host time per fit from entering the fused driver to its first dispatch,
in ms: the ``prepare_s`` that the program's ledger (``repro.obs.ledger``)
counted since the window opened, over the fits of the window.  A program
whose ledger keeps no such count gives nothing."""


def read(r):
    from repro.obs.ledger import LEDGER

    counts = getattr(LEDGER, "counts", None)
    fits = r.counters.get("fits", 0)
    if counts is None or not fits:
        return None
    return 1e3 * counts()["prepare_s"] / fits
