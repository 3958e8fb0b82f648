"""The harness: find a cell's files by name, run it, and build its result.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment;
* ``bench/traffic/<traffic>.json``: the mix, naming one of the general
  load generators of ``bench.loads`` and giving its parameters (its
  arrival law and sizes among them); a mix that needs a driver of its own
  brings it as ``bench/traffic/<traffic>.py``, a module with a class
  ``Load`` built as ``Load(config, traffic, seed, limits)``, which is
  then used in place of the named generator;
* ``bench/limits/<workload>.json``: the limit of each number that the
  cell's correctness check compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  function ``read(reading) -> float | None`` of a ``Reading``.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile

from . import loads, trace, work

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache: a fixed directory in the checkout.
CACHE_DIR = ROOT / ".jax_cache"
# A traced run profiles this many seconds in the middle of its window (at
# least one whole fit or batch), unless its traffic sets ``trace_s``: long
# enough for several dispatches, short enough that the trace stays a few
# MB and reads back in seconds.
TRACE_SECONDS = 3.0


def start_jax() -> list:
    """Import the system under test's JAX with the persistent compilation
    cache at ``CACHE_DIR`` (every executable cached, however quick its
    compile) and return ``jax.devices()``.  JAX reads the cache variable
    when it is imported, and the program's own cache helper defers to
    it."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.devices()


def load_benchmark(root=ROOT, staged: bool = False) -> dict:
    """``BENCHMARK.json``; with ``staged``, plus the entries of the cells in
    ``bench/staged.json`` that wait for their proof on the chip, for the
    tools that prove them (``bench/knee.py``, ``bench/calibrate.py``) and
    for the tests."""
    root = pathlib.Path(root)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    path = root / "bench" / "staged.json"
    if not (staged and path.is_file()):
        return doc
    extra = json.loads(path.read_text())
    for key in ("configs", "workloads"):
        names = {e["name"] for e in doc[key]}
        doc[key] += [e for e in extra[key] if e["name"] not in names]
    for key in ("end_to_end", "per_layer"):
        by_name = {m["name"]: m for m in doc[key]}
        for m in extra[key]:
            if m["name"] not in by_name:
                doc[key].append(m)
            elif "workloads" in m:
                by_name[m["name"]]["workloads"] += m["workloads"]
    return doc


def _json(kind: str, name: str, bench=BENCH) -> dict:
    path = pathlib.Path(bench) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                                f" file {path}")
    return json.loads(path.read_text())


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench=BENCH):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = pathlib.Path(bench) / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {metric!r}")
    return _module(path, f"metric_{metric}").read


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    driver: pathlib.Path | None = None    # bench/traffic/<traffic>.py


def _reports(metric: dict, name: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def resolve(bench_json: dict, workload: str, bench=BENCH) -> Cell:
    """The cell ``workload`` of ``bench_json``, with its files loaded."""
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench_json["configs"]}
    config = json.loads((pathlib.Path(bench).parent
                         / configs[w["config"]]["file"]).read_text())
    e2e = [m for m in bench_json["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench_json["per_layer"]
                 if _reports(m, workload, names)]
    tdir = pathlib.Path(bench) / "traffic"
    driver = tdir / f"{w['traffic']}.py"
    driver = driver if driver.is_file() else None
    traffic = ({} if driver is not None
               and not (tdir / f"{w['traffic']}.json").is_file()
               else _json("traffic", w["traffic"], bench))
    return Cell(w, config, traffic,
                _json("limits", workload, bench)["limits"], e2e, per_layer,
                driver)


def make_load(cell: Cell, seed: int):
    """The cell's load generator: ``bench/traffic/<traffic>.py``'s ``Load``
    where the mix brings one, else the general generator its data names."""
    if cell.driver is not None:
        cls = _module(cell.driver, f"traffic_{cell.workload['traffic']}").Load
    else:
        name = cell.traffic["load"]
        if name not in loads.LOADS:
            raise KeyError(f"traffic {cell.workload['traffic']!r} names "
                           f"unknown load generator {name!r}")
        cls = loads.LOADS[name]
    return cls(cell.config, cell.traffic, seed, cell.limits)


class Profile:
    """The profiler over a stretch of ``seconds`` of a window, written to a
    temporary directory and reduced by ``bench.trace``.  The load generator
    starts and stops it, once, on boundaries of its own work."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.started = False
        self.active = False
        self.t_start = None
        self.dir = None
        self._span = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        self._span.__enter__()
        self.started = self.active = True
        self.t_start = loads.clock()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def summary(self) -> trace.Summary:
        try:
            return trace.summarize(trace.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may read."""
    counters: dict
    trace: trace.Summary | None
    traced_work: work.Work | None
    peaks: work.Peaks


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             peaks: work.Peaks, device: dict, t_start: float,
             control: bool = False, log=None) -> dict:
    """Set up, measure, check; return the result line's object.  ``device``
    holds ``platform``, ``kind``, ``count`` and a ``peak_bytes()``
    callable."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    drv = make_load(cell, seed)
    drv.want_control = control
    drv.setup()
    setup_s = loads.clock() - t_start
    profile = (Profile(cell.traffic.get("trace_s", TRACE_SECONDS))
               if traced else None)
    win = drv.window(seconds, profile)
    peak = device["peak_bytes"]()
    drv.release()
    checks = drv.check(win)
    for note in win.notes:
        log(note)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    out = {"correct": all(c.ok for c in checks),
           "attempted": win.attempted, "failed": win.failed}
    metrics = {}
    if traced:
        summary = profile.summary()
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        reading = Reading(win.counters, summary, win.traced_work, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_gaps}
        bound = work.least_time(win.traced_work, peaks)
        log(f"traced window_s {summary.window_s:.6f} busy_s "
            f"{summary.busy_s:.6f} mttkrp_s "
            f"{summary.scope_s.get('mttkrp', 0.0):.6f} mttkrp_least_s "
            f"{bound[0]:.6e} bound {bound[1]}")
    else:
        values = dict(win.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    log(f"setup_s {setup_s:.4f} compiles_in_window "
        f"{win.counters.get('compiles')}")
    if control:
        log(f"control {json.dumps(drv.control_gap)}")
        out["control"] = drv.control_gap
    out["metrics"] = metrics
    out["device"] = dev
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r}")
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out
