"""The compile/retrace ledger: one registry for every executable cache.

The stack builds jitted executables in four places, each behind its own
``functools.lru_cache`` keyed on everything that *should* force a new
executable (backend, shapes, rank, tiling, method):

  * ``core.als_device._build_sweep_block``   — sequential fused sweeps
  * ``serve.batched_engine._build_batched_block`` — vmapped service blocks
  * ``serve.batched_engine._build_pod_block``     — pod (shard_map) blocks
  * ``core.distributed._build_dist_sweep_block``  — shard_map sweeps

The lru hit/miss counters see *builder* calls, but jit re-specializes
per concrete nnz/shape INSIDE one builder entry — the retraces the
counters structurally cannot see.  Each builder therefore registers its
jitted fn here, and the ledger reads the per-executable trace count via
jax's (version-private, best-effort) ``fn._cache_size()`` to report
actual traces as a delta since the last ``reset()``.

This replaces the old ``als_device._SWEEP_BLOCK_REGISTRY`` module-global
list: the ledger is resettable (``reset()`` re-baselines trace counts so
assertions can't leak across tests — an autouse fixture in
tests/conftest.py calls it), scoped queries (``stats(kind=...)``), and
it feeds the tracer: every registration emits a ``ledger.compile`` event
so a trace alone reconstructs the compile story.

Entries are never dropped by ``reset()``: the lru caches keep the fns
alive for the life of the process, and keeping them lets the ledger
distinguish "new block built" (``blocks_new``) from "existing block
retraced" after a reset.

The ledger also counts, per kind and since the last ``reset()``, what the
program does around its executables (``count`` / ``counts``):
``dispatches``, ``h2d_bytes`` (the bytes of the host arrays uploaded for
a call; the plan's cached uploads count once, under the kind ``plan``)
and ``prepare_s`` (host seconds from a call's entry to its first
dispatch).  They are always on: a few adds per call under the lock.  The
benchmark resets the ledger when its window opens and reads them per fit.
A kind may add counters of its own: ``pallas_gather`` counts, per trace
of the Pallas MTTKRP, the input factors gathered in HBM (``hbm``) and by
one-hot matmuls in the kernel (``onehot``); ``sweep_fit`` counts, per
trace of a fused sweep, how its fit reads ``<X, X_hat>``: ``mttkrp``
from the last mode's MTTKRP output (the inline CP sweep), ``gather``
by gathering every factor at every nonzero (method sweeps: nncp,
masked); ``sweep_block`` counts
``hbm_gather_bytes``, the factor rows the fused sweeps gathered in HBM
over the iterations each fit ran (``kernels.ops.hbm_gather_bytes``), for
the fits that gathered any.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Callable, Iterator

from . import trace as _trace

__all__ = ["RetraceLedger", "LEDGER", "COUNTERS"]

#: What ``RetraceLedger.count`` adds to for every kind, with its zero.
COUNTERS = {"dispatches": 0, "h2d_bytes": 0, "prepare_s": 0.0}


def _traces_of(fn: Any) -> int | None:
    """Actual trace count of a jitted fn via version-private jax
    introspection; None when the attribute is unavailable."""
    size: Callable[[], int] | None = getattr(fn, "_cache_size", None)
    if size is None:
        return None
    try:
        return int(size())
    except Exception:  # pragma: no cover - defensive
        return None


class RetraceLedger:
    """Thread-safe registry of (kind, key) -> jitted executable."""

    def __init__(self):
        self._lock = threading.Lock()
        # (kind, key) -> {"fn": fn, "baseline": int}
        self._entries: dict[tuple[str, str], dict] = {}
        # keys registered since the last reset()
        self._new: set[tuple[str, str]] = set()
        # kind -> COUNTERS since the last reset()
        self._counts: dict[str, dict] = {}

    # -- write side ---------------------------------------------------------

    def register(self, kind: str, key: Any, fn: Any) -> Any:
        """Record a freshly built executable.  Called from inside the
        lru-cached builders, so each (kind, key) registers at most once
        per process; re-registration just refreshes the fn.  Emits a
        ``ledger.compile`` trace event.  Returns ``fn`` for chaining."""
        k = (kind, str(key))
        base = _traces_of(fn)
        with self._lock:
            self._entries[k] = {"fn": fn, "baseline": base or 0}
            self._new.add(k)
        _trace.event("ledger.compile", cat="compile", kind=kind,
                     key=str(key))
        return fn

    def count(self, kind: str, **amounts: float) -> None:
        """Add ``amounts`` to ``kind``'s counters: ``COUNTERS`` and any
        other name the kind counts."""
        with self._lock:
            c = self._counts.get(kind)
            if c is None:
                c = self._counts[kind] = dict(COUNTERS)
            for name, amount in amounts.items():
                c[name] = c.get(name, 0) + amount

    def reset(self) -> None:
        """Re-baseline: trace counts, the new-block set and the counters
        read as zero after this, so per-test / per-run deltas are
        isolated.  Entries themselves are retained (their executables
        stay alive in the lru caches regardless)."""
        with self._lock:
            for entry in self._entries.values():
                entry["baseline"] = _traces_of(entry["fn"]) or 0
            self._new.clear()
            self._counts.clear()

    @contextmanager
    def isolated(self) -> Iterator["RetraceLedger"]:
        """Scoped isolation: reset on entry AND exit, so deltas observed
        inside the block are the block's own and nothing leaks out."""
        self.reset()
        try:
            yield self
        finally:
            self.reset()

    # -- read side ----------------------------------------------------------

    def stats(self, kind: str | None = None) -> dict:
        """``{"blocks", "blocks_new", "traces"}`` for one kind (or all).

        ``blocks`` counts registered executables, ``blocks_new`` those
        registered since the last ``reset()``, and ``traces`` sums
        per-executable trace counts as a delta since ``reset()`` — or
        None when no executable exposes the introspection attribute
        (jax version drift), so callers can skip rather than misreport.
        """
        with self._lock:
            items = [(k, e) for k, e in self._entries.items()
                     if kind is None or k[0] == kind]
            new = sum(1 for k, _ in items if k in self._new)
        total = 0
        have = False
        for _, entry in items:
            n = _traces_of(entry["fn"])
            if n is not None:
                have = True
                total += max(n - entry["baseline"], 0)
        return {"blocks": len(items), "blocks_new": new,
                "traces": total if have else None}

    def entries(self, kind: str | None = None) -> list[dict]:
        """Per-executable rows for the report: kind, key, trace delta."""
        with self._lock:
            items = sorted(
                (k, e) for k, e in self._entries.items()
                if kind is None or k[0] == kind)
        out = []
        for (knd, key), entry in items:
            n = _traces_of(entry["fn"])
            out.append({
                "kind": knd,
                "key": key,
                "traces": None if n is None else max(n - entry["baseline"], 0),
            })
        return out

    def counts(self, kind: str | None = None) -> dict:
        """Counters since the last ``reset()``: one kind's ``COUNTERS`` and
        its own counters, or ``COUNTERS`` summed over all kinds."""
        with self._lock:
            if kind is not None:
                return {**COUNTERS, **self._counts.get(kind, {})}
            out = dict(COUNTERS)
            for c in self._counts.values():
                for name in out:
                    out[name] += c[name]
        return out

    def kinds(self) -> list[str]:
        with self._lock:
            return sorted({k for k, _ in self._entries})


#: The process-wide ledger every builder registers into.
LEDGER = RetraceLedger()
