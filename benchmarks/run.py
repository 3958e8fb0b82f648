"""Benchmark runner: one section per paper table/figure + kernel + roofline
+ the system layers (ALS engines, serving, distributed, methods).

``PYTHONPATH=src python -m benchmarks.run``               — everything
``PYTHONPATH=src python -m benchmarks.run fig3 fig5``     — a subset
``PYTHONPATH=src python -m benchmarks.run methods --smoke`` — CI-sized

Output: ``name,us_per_call,derived`` CSV per section, plus one
machine-readable ``results/BENCH_<name>.json`` per section run —
{config, rows (with plan fingerprints where the section reports them),
wall time, timestamp} — so the perf trajectory is trackable across PRs
instead of living in scrollback.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import clock as obs_clock
from repro.obs import history as obs_history

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[1] / "results"
HISTORY_PATH = RESULTS_DIR / "BENCH_history.jsonl"


def _clean(obj):
    if isinstance(obj, dict):
        return {str(k): _clean(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_clean(v) for v in obj]
    if hasattr(obj, "item"):          # numpy scalars
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def emit_json(name: str, wall_s: float, rows, config: dict) -> pathlib.Path:
    """Write one section's machine-readable result file AND append the
    same (provenance-stamped) payload to the append-only history ledger.
    ``rows`` is the section's structured output (list of dicts) when it
    provides one, else None — wall time, config, and provenance are
    always recorded."""
    from . import common

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = RESULTS_DIR / f"BENCH_{name}.json"
    prov = common.provenance()
    rows = _clean(rows)
    config = _clean(config)

    path.write_text(json.dumps({
        "name": name,
        "config": config,
        "wall_s": wall_s,
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "provenance": prov,
    }, indent=2) + "\n")
    record = obs_history.make_record(
        name, rows=rows if isinstance(rows, list) else None,
        wall_s=wall_s, config=config, provenance=prov)
    obs_history.append(HISTORY_PATH, record)
    return path


_SECTIONS = ("table3", "fig3", "fig4", "fig5", "kernel", "als", "serve",
             "methods", "dist", "pod", "roofline", "obs")
_FLAGS = ("--smoke",)

# The streaming row once buried a 370x retrace regression behind a bare
# speedup number.  These fields are the regression's witnesses (hit rate,
# per-increment cost, host merge cost); a methods run whose streaming row
# lacks any of them fails the whole runner loudly.
_STREAMING_REQUIRED = ("cache_hit_rate", "s_per_increment", "host_merge_s")


def _check_methods_rows(rows) -> None:
    streaming = [r for r in (rows or [])
                 if isinstance(r, dict)
                 and r.get("name") == "methods/streaming"]
    if not streaming:
        sys.exit("methods section produced no 'methods/streaming' row")
    missing = [f for f in _STREAMING_REQUIRED if f not in streaming[0]]
    if missing:
        sys.exit(f"methods/streaming row is missing required fields "
                 f"{missing}; present: {sorted(streaming[0])}")


# The obs section's witnesses: every backend row must carry a finite
# predicted-vs-observed ratio, every imbalance row the measured factor,
# and the ledger row must stay under its recompile ceiling (a fresh
# process traces each executable at most once — more is a retrace leak).
def _check_obs_rows(rows) -> None:
    rows = [r for r in (rows or []) if isinstance(r, dict)]
    ratio = [r for r in rows if r.get("section") == "ratio"]
    imb = [r for r in rows if r.get("section") == "imbalance"]
    ledger = [r for r in rows if r.get("section") == "ledger"]
    if not ratio or not imb or not ledger:
        sys.exit(f"obs section missing row kinds: ratio={len(ratio)} "
                 f"imbalance={len(imb)} ledger={len(ledger)}")
    for r in ratio:
        po = r.get("predicted_over_observed")
        if not isinstance(po, float) or not (po > 0.0):
            sys.exit(f"obs row {r.get('name')} has no positive "
                     f"predicted_over_observed (got {po!r})")
    for r in imb:
        if not isinstance(r.get("max_measured_imbalance"), float):
            sys.exit(f"obs row {r.get('name')} lacks "
                     f"max_measured_imbalance")
    led = ledger[0]
    traces, ceiling = led.get("traces"), led.get("expected_max_traces")
    if traces is not None and traces > ceiling:
        sys.exit(f"retrace ledger over ceiling: {traces} traces for "
                 f"{ceiling} executables — a jit cache is re-specializing")


# The pod section's witnesses: a multi-window run costs ONE dispatch
# (host_syncs == 1, exactly one pod.dispatch span), the double-buffered
# stream hid some host assembly behind device compute (overlap fraction
# > 0), and the pod-block executables stayed under the retrace ceiling.
def _check_pod_rows(rows) -> None:
    rows = [r for r in (rows or []) if isinstance(r, dict)]
    by_name = {r.get("name"): r for r in rows}
    disp = by_name.get("pod/one-dispatch")
    if not disp:
        sys.exit("pod section produced no 'pod/one-dispatch' row")
    if disp.get("pod_dispatch_spans") != 1 or disp.get("host_syncs") != 1:
        sys.exit(f"pod multi-window run was not one dispatch: {disp}")
    if not disp.get("windows", 0) > 1:
        sys.exit(f"pod dispatch ran {disp.get('windows')} windows — the "
                 f"one-dispatch witness needs a MULTI-window run")
    over = by_name.get("pod/overlap")
    if not over:
        sys.exit("pod section produced no 'pod/overlap' row")
    if not (isinstance(over.get("overlap_fraction"), float)
            and over["overlap_fraction"] > 0.0):
        sys.exit(f"double-buffered stream showed no assembly/compute "
                 f"overlap: {over}")
    agree = by_name.get("pod/agreement")
    if not agree or not agree.get("max_fit_err", 1.0) < 1e-3:
        sys.exit(f"pod vs single-device agreement failed: {agree}")
    lane = by_name.get("pod/lane-placement")
    if not lane:
        sys.exit("pod section produced no 'pod/lane-placement' row")
    if not (isinstance(lane.get("imbalance"), float)
            and lane["imbalance"] <= lane.get("imbalance_contiguous",
                                              0.0) + 1e-9):
        sys.exit(f"load-aware lane placement did not improve on the "
                 f"contiguous split: {lane}")
    led = by_name.get("pod/ledger")
    if not led:
        sys.exit("pod section produced no 'pod/ledger' row")
    traces, ceiling = led.get("traces"), led.get("expected_max_traces")
    if traces is not None and traces > ceiling:
        sys.exit(f"pod-block retrace ledger over ceiling: {traces} traces "
                 f"for {ceiling} executables")


def main() -> None:
    enable_compile_cache()
    argv = sys.argv[1:]
    flags = {a for a in argv if a.startswith("--")}
    want = {a for a in argv if not a.startswith("--")}
    # A typo must fail loudly, not select zero sections and exit 0 green.
    unknown = sorted((want - set(_SECTIONS)) | (flags - set(_FLAGS)))
    if unknown:
        sys.exit(f"unknown section/flag {unknown}; sections: "
                 f"{', '.join(_SECTIONS)}; flags: {', '.join(_FLAGS)}")
    smoke = "--smoke" in flags

    def on(name):
        return not want or name in want

    # (name, title, fn) — fn returns structured rows or None.
    sections = []
    if on("table3"):
        from . import table3_datasets
        sections.append(("table3", "table3 (dataset characteristics)",
                         table3_datasets.main))
    if on("fig3"):
        from . import fig3_total_time
        sections.append(("fig3", "fig3 (total execution time vs baselines)",
                         fig3_total_time.main))
    if on("fig4"):
        from . import fig4_load_balance
        sections.append(("fig4", "fig4 (adaptive load balancing ablation)",
                         fig4_load_balance.main))
    if on("fig5"):
        from . import fig5_memory
        sections.append(("fig5", "fig5 (memory consumption)",
                         fig5_memory.main))
    if on("kernel"):
        from . import kernel_bench
        sections.append(("kernel", "pallas kernel micro-bench",
                         kernel_bench.main))
    if on("als"):
        from . import als_bench
        sections.append(("als", "ALS engine (fused device-resident vs "
                         "host loop)", als_bench.main))
    if on("serve"):
        from . import serve_bench
        # own argv: the runner's section args must not leak into
        # serve_bench's argparse, and its timing-dependent acceptance
        # assertions must not abort the remaining sections
        serve_args = ["--no-check"] + (["--smoke"] if smoke else [])
        sections.append(("serve", "serving (batched service vs sequential "
                         "runner)", lambda: serve_bench.main(serve_args)))
    if on("methods"):
        from . import methods_bench
        sections.append(("methods", "decomposition methods (nncp / masked "
                         "/ streaming / mixed-method service)",
                         lambda: methods_bench.main(
                             ["--smoke"] if smoke else [])))
    if on("dist"):
        from . import dist_bench
        # subprocess with forced host devices: jax pins its device count
        # at first init, so the 8-device mesh cannot share this process
        sections.append(("dist", "distributed ALS smoke (shard_map, 8 "
                         "virtual devices)", dist_bench.main))
    if on("pod"):
        from . import pod_bench
        # subprocess like dist: the 8-device batch mesh cannot share a
        # process whose jax already pinned its device count
        sections.append(("pod", "pod serving (mesh-sharded batch, "
                         "on-device convergence, double-buffered dispatch)",
                         lambda: pod_bench.main(["--smoke"] if smoke
                                                else [])))
    if on("roofline"):
        from . import roofline
        sections.append(("roofline", "roofline table (from dry-run)",
                         roofline.main))
    if on("obs"):
        from . import obs_bench
        sections.append(("obs", "observability (cost model vs measured, "
                         "trace artifacts)",
                         lambda: obs_bench.main(["--smoke"] if smoke
                                                else [])))

    for name, title, fn in sections:
        print(f"\n===== {title} =====")
        if name in ("dist", "pod"):
            from . import common
            if common.holds_accelerator():
                print(f"===== skipped: {name} {common.HOST_CHILD_REFUSAL} "
                      f"=====")
                continue
        t0 = obs_clock.now()
        rows = fn()
        wall = obs_clock.now() - t0
        if name == "methods":
            _check_methods_rows(rows if isinstance(rows, list) else None)
        if name == "obs":
            _check_obs_rows(rows if isinstance(rows, list) else None)
        if name == "pod":
            _check_pod_rows(rows if isinstance(rows, list) else None)
        path = emit_json(name, wall, rows if isinstance(rows, list) else None,
                         {"argv": argv, "smoke": smoke})
        print(f"===== done in {wall:.1f}s -> {path.relative_to(path.parents[1])} =====")


if __name__ == "__main__":
    main()
