"""Unified telemetry: cross-layer tracing, compile/retrace accounting,
and the cost-model-vs-measured validation harness.

Three pieces, consumed by every layer of the stack:

  * ``obs.trace`` — a low-overhead span/event recorder.  Choke points
    across the stack (plan construction, slab packing, fused-sweep
    window dispatch, batched-service flushes, distributed windows,
    streaming increments) report into the ACTIVE tracer when one is
    installed and pay a single ``is None`` check and a profiler probe
    when none is (the tracing-disabled hot path adds zero allocations
    per dispatch — enforced by test).  While a JAX profiler session
    captures, every span is also a profiler host event, beside the
    device operations.  Traces export as JSONL or Chrome-trace JSON
    (viewable in ``about:tracing`` / Perfetto).
  * ``obs.ledger`` — ONE compile/retrace ledger keyed by executable
    cache: every jitted block builder (sequential sweep, vmapped
    batched, pod, distributed shard_map) registers its executables
    here, and per-executable trace counts expose retraces the lru
    hit/miss counters structurally cannot see; per-kind counters of
    dispatches, uploaded bytes and preparation time ride along.
    Resettable and test-isolated (autouse fixture in tests/conftest.py).
  * ``obs.calibrate`` + ``benchmarks/obs_bench.py`` — replays the
    Table-3 generators per backend, joins predicted cost from the
    GPU-architectural model against measured span durations, and emits
    ``results/BENCH_obs.json`` (predicted-vs-observed ratio, per-mode
    load-imbalance factor, compile-vs-steady breakdown).

The perf-sentinel layer rides on the same artifacts:

  * ``obs.history`` — the append-only benchmark-history ledger
    (``results/BENCH_history.jsonl``): every ``benchmarks/run.py``
    section appends one schema-validated, provenance-stamped record
    (git sha, UTC timestamp, host, jax/device versions, rows, plan
    fingerprints).  ``python -m repro.obs.history validate`` is the CI
    schema gate.
  * ``obs.regress`` — the noise-aware regression gate: direction-aware
    per-metric specs, min/max-of-k best aggregation over the ledger's
    last k runs, tolerance bands widened by observed jitter but capped
    so a 2x shift always fails.  ``python -m repro.obs.regress --check``
    gates CI against the committed ``results/BENCH_baseline.json``;
    ``--update-baseline`` refreshes it.
  * ``obs.health`` — live serving SLO health: ``SLOPolicy`` targets
    (per-bucket p99 latency, queue depth/age, cache-hit / overlap /
    occupancy floors) judged against ``ServiceMetrics.snapshot()``
    views, with edge-triggered ``health.breach`` / ``health.clear``
    trace events so a JSONL trace alone reconstructs every incident.

``python -m repro.obs.report <file>`` renders any JSONL trace, Chrome
trace, or BENCH json as a terminal dashboard; ``--history`` adds trend
tables over the history ledger.

``obs.clock`` is the one monotonic-clock front door (``perf_counter``)
every layer times durations through; ``clock.wall`` is the epoch clock
for timestamps only.

Import discipline: this package's core (``trace``, ``ledger``,
``clock``, ``history``, ``regress``, ``health``) depends on the stdlib
only, so ``repro.core`` and ``repro.kernels`` can import it without
cycles; ``obs.calibrate`` and ``obs.report`` import the rest of the
stack and are therefore NOT imported here eagerly.
``history`` and ``regress`` double as ``python -m`` entrypoints, so they
(and ``health``, for symmetry) are imported explicitly
(``from repro.obs import health``), not eagerly here — an eager package
import of a ``-m`` target trips the runpy double-import warning.
"""
from . import clock  # noqa: F401
from .ledger import LEDGER, RetraceLedger  # noqa: F401
from .trace import (Tracer, active, capture, disable, enable, event,  # noqa: F401
                    load_jsonl, span, validate_chrome)

__all__ = [
    "clock", "LEDGER", "RetraceLedger", "Tracer", "active", "capture",
    "disable", "enable", "event", "load_jsonl", "span", "validate_chrome",
]
