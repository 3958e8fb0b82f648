"""Reduction of a JAX profiler trace to device busy, idle and scope time.

``jax.profiler.stop_trace`` writes ``<dir>/plugins/profile/<run>/`` with
an ``.xplane.pb`` and its conversion to the Chrome trace format,
``.trace.json.gz``.  The Chrome file is what is read here: it carries,
for every XLA operation that ran on a device, the ``tf_op`` argument, the
``jax.named_scope`` path of the operation (``jit(run_block)/while/body/
closed_call/mttkrp/scatter-add:``), which the Python ``ProfileData`` view
of the ``.xplane.pb`` does not expose.

* Device operations are the events of the ``XLA Ops`` thread of each
  ``/device:<kind>:<n>`` process.  Control operations (``while``,
  ``conditional``, ``call``) contain other operations: they count towards
  busy time, where intervals are merged, but not towards the list of
  operations that took most time.
* The traced window is the host span named ``bench.window``, which the
  harness opens around the part of its window that it traces.  The host
  thread that holds it is the benchmark's Python thread, whatever the
  profiler names it (after the interpreter: ``python``, ``python3``).
* Busy time is the length of the union of device operation intervals
  inside the window, averaged over the devices; the idle share is one
  minus busy time over the window.
* A scope's time is the union of the intervals of operations whose
  ``tf_op`` path has the scope as a component, alone (``mttkrp``) or under
  a transformation (``vmap(mttkrp)``).
* An idle gap is named by what the host was doing: the shortest event of
  the host's Python thread that covers the gap's midpoint and lasts at
  least half the gap.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import pathlib
import re

WINDOW_SPAN = "bench.window"
_CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass(frozen=True)
class Op:
    start: float          # microseconds, on the trace's common clock
    end: float
    name: str             # scope path where known, else the HLO name
    category: str


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Op]]        # device process name -> ops
    host: list[tuple[float, float, str]]   # Python thread events
    window: tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    scope_s: dict[str, float]
    top_ops: list[list]                    # [[name, seconds], ...]
    idle_gaps: list[list]                  # [[host activity, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace_file(dump_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(dump_dir).glob(
        "plugins/profile/*/*.trace.json.gz"))
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {dump_dir}")
    return files[-1]


def load(path) -> Trace:
    """Read a ``.trace.json.gz`` (or a profiler dump directory)."""
    path = pathlib.Path(path)
    if path.is_dir():
        path = find_trace_file(path)
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    device_ops: dict[str, list[Op]] = collections.defaultdict(list)
    host_threads = collections.defaultdict(list)
    windows = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        thread = threads.get((e["pid"], e["tid"]), "")
        start, end = float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0))
        if (proc.startswith("/device:") and "CUSTOM" not in proc
                and thread == "XLA Ops"):
            args = e.get("args", {})
            device_ops[proc].append(Op(
                start, end, args.get("tf_op") or e["name"],
                args.get("hlo_category", "")))
        elif proc.startswith("/host:"):
            if e["name"] == WINDOW_SPAN:
                windows.append(((start, end), (e["pid"], e["tid"])))
            else:
                host_threads[(e["pid"], e["tid"])].append(
                    (start, end, e["name"]))
    if not windows:
        raise ValueError(f"{path} has no {WINDOW_SPAN!r} span")
    if not device_ops:
        raise ValueError(f"{path} has no device operations")
    window, python_thread = windows[0]
    return Trace(dict(device_ops), host_threads[python_thread], window)


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def in_scope(name: str, scope: str) -> bool:
    """Whether a ``tf_op`` path has ``scope`` as one of its components."""
    pat = re.compile(rf"^(\w+\()*{re.escape(scope)}\)*:?$")
    return any(pat.match(part) for part in name.split("/"))


def _host_activity(host, s: float, e: float) -> str:
    """``<benchmark call> > <innermost Python event>`` at the gap."""
    mid, need = (s + e) / 2.0, (e - s) / 2.0
    inner = call = None
    for hs, he, name in host:
        if not hs <= mid <= he:
            continue
        if name.startswith("bench.") and (call is None
                                          or he - hs < call[0]):
            call = (he - hs, name)
        if he - hs >= need and (inner is None or he - hs < inner[0]):
            inner = (he - hs, name)
    parts = [p[1] for p in (call, inner) if p is not None]
    return " > ".join(dict.fromkeys(parts)) or "host: no Python span"


def summarize(trace: Trace, scopes=("mttkrp",), top: int = 10) -> Summary:
    lo, hi = trace.window
    window_s = (hi - lo) / 1e6
    busy, scope_us = [], collections.Counter()
    for ops in trace.device_ops.values():
        spans = [(o.start, o.end) for o in ops]
        busy.append(union_length(spans, lo, hi))
        for sc in scopes:
            scope_us[sc] += union_length(
                [(o.start, o.end) for o in ops if in_scope(o.name, sc)],
                lo, hi)
    ndev = len(trace.device_ops)
    per_op = collections.Counter()
    for ops in trace.device_ops.values():
        for o in ops:
            if o.category not in _CONTAINERS:
                per_op[o.name] += max(min(o.end, hi) - max(o.start, lo), 0.0)
    top_ops = [[n, us / 1e6 / ndev] for n, us in per_op.most_common(top)]
    # Idle gaps of the first device, the longest first.
    first = next(iter(trace.device_ops.values()))
    holes = sorted(gaps([(o.start, o.end) for o in first], lo, hi),
                   key=lambda g: g[0] - g[1])[:top]
    idle = [[_host_activity(trace.host, s, e), (e - s) / 1e6]
            for s, e in holes]
    return Summary(window_s, sum(busy) / ndev / 1e6,
                   {k: v / ndev / 1e6 for k, v in scope_us.items()},
                   top_ops, idle)
