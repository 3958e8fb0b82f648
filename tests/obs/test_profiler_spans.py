"""The program's spans on the JAX profiler's clock: a profiler capture of a
tiny fit holds the fused driver's spans as host events under their bare
names, properly nested; with no capture and no tracer, ``active()`` is
None; and the bridge feeds both kinds of span."""
import gzip
import json
import pathlib

import jax
import pytest

from repro.core import cpd_als, random_sparse
from repro.obs import trace as obs_trace

FIT_SPANS = ("als.fit", "als.prepare", "als.window", "als.dispatch",
             "als.fetch", "als.readback")


def _host_events(dump_dir) -> list[dict]:
    """Complete events of the written ``.trace.json.gz`` on host threads."""
    (path,) = pathlib.Path(dump_dir).glob("plugins/profile/*/*.trace.json.gz")
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    return [e for e in events if e.get("ph") == "X"
            and procs.get(e["pid"], "").startswith("/host:")]


def _inside(inner: dict, outer: dict) -> bool:
    return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


def _profiled_fit(dump_dir, tol=1e-12, **kw):
    """Three one-sweep windows; the default tolerance is above 0, so every
    window fetches its fit, and three sweeps of random data never meet
    it."""
    t = random_sparse((10, 8, 6), 200, seed=3)
    jax.profiler.start_trace(str(dump_dir))
    try:
        res = cpd_als(t, 4, n_iters=3, tol=tol, check_every=1, **kw)
    finally:
        jax.profiler.stop_trace()
    return res, _host_events(dump_dir)


def test_capture_holds_the_fit_spans_by_bare_name(tmp_path):
    assert obs_trace.installed() is None
    res, events = _profiled_fit(tmp_path)
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    for name in FIT_SPANS:
        assert name in by_name, f"no {name} host event"
    assert not any("#" in e["name"] for e in events
                   if e["name"].startswith("als."))
    (fit,) = by_name["als.fit"]
    (prep,) = by_name["als.prepare"]
    (back,) = by_name["als.readback"]
    assert _inside(prep, fit) and _inside(back, fit)
    assert prep["ts"] + prep["dur"] <= min(w["ts"]
                                           for w in by_name["als.window"])
    windows = by_name["als.window"]
    assert len(windows) == res.iters == 3
    for w in windows:
        assert _inside(w, fit)
        assert sum(_inside(d, w) for d in by_name["als.dispatch"]) == 1
        assert sum(_inside(f, w) for f in by_name["als.fetch"]) == 1
    # Span arguments ride along as the event's args.
    assert fit["args"]["backend"] == "segment"
    assert int(fit["args"]["nnz"]) == 200
    assert int(prep["args"]["h2d_bytes"]) > 0


def test_a_run_that_cannot_stop_fetches_no_fit(tmp_path):
    """With the tolerance off the windows are dispatched back to back: no
    ``als.fetch`` span, one host sync, the fits read back at the end."""
    res, events = _profiled_fit(tmp_path, tol=-1.0)
    names = [e["name"] for e in events]
    assert names.count("als.window") == names.count("als.dispatch") == 3
    assert "als.fetch" not in names
    assert names.count("als.readback") == 1
    assert res.host_syncs == 1 and len(res.fits) == 3


def test_no_capture_no_tracer_means_no_spans():
    assert obs_trace.active() is None
    assert obs_trace.span("als.fit") is obs_trace.NULL


def test_installed_tracer_records_and_feeds_the_profiler(tmp_path):
    with obs_trace.capture("both") as tr:
        _, events = _profiled_fit(tmp_path)
    recorded = {r["name"] for r in tr.records() if r["kind"] == "span"}
    assert set(FIT_SPANS) <= recorded
    assert set(FIT_SPANS) <= {e["name"] for e in events}
    prep = next(r for r in tr.records() if r["name"] == "als.prepare")
    (prof,) = [e for e in events if e["name"] == "als.prepare"]
    assert int(prof["args"]["h2d_bytes"]) == prep["args"]["h2d_bytes"]


class _FakeTraceMe:
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def set_metadata(self, **args):
        self.args.update(args)

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, dict(self.args)))


@pytest.fixture
def fake_bridge(monkeypatch):
    """A capture that is always open, feeding ``_FakeTraceMe``."""
    _FakeTraceMe.log = []
    monkeypatch.setattr(obs_trace, "_capturing", lambda: True)
    monkeypatch.setattr(obs_trace, "_traceme", _FakeTraceMe)
    return _FakeTraceMe.log


def test_profiler_only_spans_carry_set_attrs(fake_bridge):
    tr = obs_trace.active()
    assert tr is not None and obs_trace.installed() is None
    with obs_trace.span("plan.upload", what="arrays") as sp:
        sp.set(h2d_bytes=12)
    tr.event("ledger.compile", kind="k")
    assert fake_bridge == [
        ("enter", "plan.upload"),
        ("exit", "plan.upload", {"what": "arrays", "h2d_bytes": 12}),
        ("enter", "ledger.compile"),
        ("exit", "ledger.compile", {"kind": "k"}),
    ]


def test_recorded_spans_open_a_traceme_on_enter(fake_bridge):
    with obs_trace.capture() as tr:
        with obs_trace.active().span("als.window", window=0) as sp:
            assert fake_bridge == [("enter", "als.window")]
            sp.set(sweeps=2)
    assert fake_bridge[-1] == ("exit", "als.window",
                               {"window": 0, "sweeps": 2})
    (rec,) = tr.records()
    assert rec["args"] == {"window": 0, "sweeps": 2}
