"""``bench.work`` counts by hand, ``bench.trace`` on a synthetic and on a
recorded trace, and the peak table."""
import gzip
import json
import pathlib

import numpy as np
import pytest

from bench import harness, trace, work
from bench_cases import tiny_cell

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "tiny_als.trace.json.gz"


def test_mttkrp_work_matches_a_hand_count():
    # 3 modes of 3, 4 and 5 rows, 7 nonzeros, rank 2, mode 0: 7 x 2 x 3
    # operations; 7 x (3 x 4 + 4) coordinate and value bytes, 4 x 2 x
    # (4 + 5) input factor bytes, 4 x 2 x 3 output bytes.
    w = work.mttkrp_work((3, 4, 5), 7, 2, 0)
    assert w.operations == 42
    assert w.bytes == 112 + 72 + 24
    sweep = work.sweep_work((3, 4, 5), 7, 2)
    assert sweep.operations == 3 * 42
    assert sweep.bytes == 3 * 112 + 3 * 4 * 2 * (3 + 4 + 5)


def test_chicago_sweep_is_hbm_bound():
    w = work.sweep_work((6186, 24, 77, 32), 5_330_673, 32)
    t, bound = work.least_time(w, work.load_peaks("TPU v5 lite"))
    assert bound == "hbm"
    assert t == pytest.approx(w.bytes / 819e9)
    assert 4.2e8 < w.bytes < 4.4e8


class _Traced:
    """A profile stand-in that marks every fit of the window as traced."""
    seconds = float("inf")
    started = active = False
    t_start = None

    def start(self):
        self.started = self.active = True
        self.t_start = 0.0

    def stop(self):
        self.active = False


def test_both_backends_count_the_same_work():
    counted = []
    for name in ("chicago-als.segment", "chicago-als.pallas"):
        cell = tiny_cell(name)
        drv = harness.make_load(cell, seed=5)
        drv.setup()
        win = drv.window(0.2, _Traced())
        t = drv.tensor
        per_fit = work.sweep_work(t.shape, t.nnz, drv.rank).scaled(
            drv.n_iters)
        assert win.traced_work == per_fit.scaled(win.counters["fits"])
        counted.append(per_fit)
    assert counted[0] == counted[1]


def _write_trace(path, device_ops, host, window, python="python",
                 other_host=()):
    """A Chrome trace in the profiler's layout: one TPU with an ``XLA
    Ops`` thread, one host with a Python thread named ``python`` and a
    runtime thread holding ``other_host``."""
    ev = [{"ph": "M", "name": "process_name", "pid": 1,
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "name": "thread_name", "pid": 1, "tid": 3,
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "name": "process_name", "pid": 2,
           "args": {"name": "/host:CPU"}},
          {"ph": "M", "name": "thread_name", "pid": 2, "tid": 9,
           "args": {"name": python}},
          {"ph": "M", "name": "thread_name", "pid": 2, "tid": 11,
           "args": {"name": "pjrt-tpu-tasks/321"}}]
    for ts, dur, name in other_host:
        ev.append({"ph": "X", "pid": 2, "tid": 11, "ts": ts, "dur": dur,
                   "name": name})
    for ts, dur, op, cat in device_ops:
        ev.append({"ph": "X", "pid": 1, "tid": 3, "ts": ts, "dur": dur,
                   "name": op, "args": {"tf_op": op, "hlo_category": cat}})
    for ts, dur, name in host + [(window[0], window[1] - window[0],
                                  trace.WINDOW_SPAN)]:
        ev.append({"ph": "X", "pid": 2, "tid": 9, "ts": ts, "dur": dur,
                   "name": name})
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": ev}, fh)


def test_synthetic_trace_reduces_to_hand_numbers(tmp_path):
    ops = [(0, 1000, "jit(f)/while/body/mttkrp/gather:", "custom fusion"),
           (500, 1000, "jit(f)/while/body/solve/dot:", "loop fusion"),
           (2000, 2000, "jit(f)/vmap(mttkrp)/scatter-add:", "custom fusion"),
           (0, 9000, "while", "while"),          # contains the others
           (12000, 500, "jit(f)/fit/mul:", "loop fusion")]
    host = [(4000, 8000, "bench.poll"), (4000, 7900, "$engine.py:1 assemble")]
    path = tmp_path / "t.trace.json.gz"
    _write_trace(path, ops, host, (-1000, 14000))
    s = trace.summarize(trace.load(path))
    assert s.window_s == pytest.approx(15000e-6)
    assert s.busy_s == pytest.approx((9000 + 500) * 1e-6)    # union
    assert s.idle_share == pytest.approx(1 - 9500 / 15000)
    assert s.scope_s["mttkrp"] == pytest.approx(3000e-6)
    assert s.top_ops[0] == ["jit(f)/vmap(mttkrp)/scatter-add:",
                            pytest.approx(2000e-6)]
    assert all(name != "while" for name, _ in s.top_ops)
    # Gaps: [-1000, 0], [9000, 12000], [12500, 14000]; the longest first,
    # named by the benchmark call and the innermost host event.
    assert s.idle_gaps[0] == ["bench.poll > $engine.py:1 assemble",
                              pytest.approx(3000e-6)]
    assert len(s.idle_gaps) == 3


@pytest.mark.parametrize("python", ["python3", "python3.12"])
def test_python_thread_is_the_one_holding_the_window_span(tmp_path, python):
    """The profiler names the host's Python thread after the interpreter
    the run was started with; the reduction finds it by the window span,
    and names idle gaps by its events, not by another thread's."""
    ops = [(0, 1000, "jit(f)/mttkrp/gather:", "custom fusion")]
    host = [(1000, 3000, "bench.fit")]
    other = [(900, 4000, "ExecuteOnDevice")]
    path = tmp_path / "t.trace.json.gz"
    _write_trace(path, ops, host, (0, 4000), python=python,
                 other_host=other)
    tr = trace.load(path)
    assert tr.window == (0.0, 4000.0)
    assert tr.host == [(1000.0, 4000.0, "bench.fit")]
    s = trace.summarize(tr)
    assert s.idle_gaps == [["bench.fit", pytest.approx(3000e-6)]]


def test_scope_matching():
    assert trace.in_scope("jit(run_block)/while/body/mttkrp/scatter:",
                          "mttkrp")
    assert trace.in_scope("jit(run_block)/vmap(mttkrp)/jit(_take)/gather:",
                          "mttkrp")
    assert not trace.in_scope("jit(run_block)/fit/gather:", "mttkrp")
    assert not trace.in_scope("jit(run_block)/mttkrp_replay/x:", "mttkrp")


def test_recorded_trace_of_two_fused_sweeps():
    """A trace recorded on a TPU v5e by the JAX profiler: one fused
    ``cpd_als`` of two segment sweeps of a 3,000-nnz tensor, rank 8, under
    a ``bench.fit`` annotation; the ``bench.window`` span the harness
    opens was added with the same bounds.  Busy time is the union of the
    device operation intervals, and the ``mttkrp`` scope covers exactly
    the operations whose scope path names it."""
    tr = trace.load(RECORDED)
    s = trace.summarize(tr)
    (ops,) = tr.device_ops.values()
    lo, hi = tr.window
    inside = [o for o in ops if lo <= o.start and o.end <= hi]
    assert len(inside) == len(ops)
    # Device operations of one stream do not overlap once the control
    # operations that contain others are set aside.
    leaf = sorted((o for o in ops if o.category not in ("while",
                                                        "conditional")),
                  key=lambda o: o.start)
    assert all(a.end <= b.start + 1e-3 for a, b in zip(leaf, leaf[1:]))
    grid = np.zeros(int(hi - lo) * 10 + 1, bool)      # 0.1 us cells
    for o in ops:
        grid[int((o.start - lo) * 10):int((o.end - lo) * 10)] = True
    assert s.busy_s == pytest.approx(grid.sum() / 1e7, rel=1e-3)
    mttkrp = sum(o.end - o.start for o in leaf if "/mttkrp/" in o.name)
    assert s.scope_s["mttkrp"] == pytest.approx(mttkrp / 1e6, rel=1e-6)
    assert 0 < s.scope_s["mttkrp"] < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)


def test_peak_table_refuses_an_unknown_device_kind():
    assert work.load_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("TPU v99 imaginary")
