"""The two general load generators: back-to-back fits, and an open-loop
request stream.

A traffic file names its load generator (``"load"``) and gives its parameters;
a configuration file gives the deployment.  The open loop's arrival law
(``"arrival_law"``) and its law of request sizes (``"nnz_law"``, else the
configuration's ``nnz_jitter``) are data too, so a new mix of arrivals or
sizes is a new traffic file.  Each generator has three phases, which the
harness calls in order:

* ``setup()``: generate the data from the seed, build what a user builds
  once, and run every executable the window will use once, so that
  nothing compiles inside the window;
* ``window(seconds, profile)``: the measured part, which returns a
  ``Window``: end-to-end values, the counters that per-layer metrics read,
  and the MTTKRP work that ran inside the traced part;
* ``check(window)``: once the window has closed and the program's device
  state is freed, compare what the timed path returned with the plain
  reference (``bench.reference``) and return the numbers compared.

The program is imported inside these functions, at the boundary: the
benchmark hands it generated tensors and reads back its answers and
counters.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from . import data, reference, work

SEED_MAX = 2**31 - 1


def clock() -> float:
    return time.perf_counter()


def trace_offset(seconds: float, trace_s: float) -> float:
    """Where the traced stretch starts in a window: centred, so that an
    open loop above capacity is traced with its queue built up, not while
    it fills from empty."""
    return max(0.0, (float(seconds) - float(trace_s)) / 2.0)


@dataclasses.dataclass
class Comparison:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    e2e: dict                      # end-to-end metric name -> value
    counters: dict                 # what per-layer readers read
    attempted: int
    failed: int
    traced_work: work.Work | None = None
    notes: list = dataclasses.field(default_factory=list)


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _fit_gap(got, want) -> float:
    """Largest gap between two fit histories; inf if their lengths differ
    or either holds a value that is not finite."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not (np.all(np.isfinite(got))
                                       and np.all(np.isfinite(want))):
        return math.inf
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def _sound(result, n_iters: int) -> bool:
    """A returned decomposition ran every iteration and holds finite
    numbers."""
    return (result.iters == n_iters and len(result.fits) == n_iters
            and bool(np.all(np.isfinite(result.fits)))
            and all(bool(np.all(np.isfinite(f))) for f in result.factors))


# Nonzero coordinates at which a FROSTT-scale model is compared.
MODEL_SAMPLE = 1 << 16


def _model_gap(got_factors, got_weights, want_factors, want_weights,
               coords) -> float:
    """Relative L2 gap of two CP models at ``coords``."""
    got = reference.model_at(got_factors, got_weights, coords)
    want = reference.model_at(want_factors, want_weights, coords)
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


class _ReferencePair:
    """The plain reference at the configuration's precision, and with
    ``control`` its bfloat16 control, compared fit by fit."""

    NAMES = ("fit_gap", "model_gap")

    def __init__(self, make_kernels, values, rank: int, n_iters: int,
                 control: bool):
        self.ref = make_kernels("float32")
        self.ctl = make_kernels("bfloat16") if control else None
        self.norm_sq = float(np.sum(np.asarray(values, np.float64) ** 2))
        self.rank, self.n_iters = rank, n_iters
        self.gaps = dict.fromkeys(self.NAMES, 0.0)
        self.control = dict.fromkeys(self.NAMES, 0.0)

    def _gaps(self, fits, factors, weights, want, coords) -> dict:
        return {"fit_gap": _fit_gap(fits, want[0]),
                "model_gap": _model_gap(factors, weights, want[1], want[2],
                                        coords)}

    def compare(self, result, seed: int, coords) -> None:
        want = reference.cp_als(self.ref, self.norm_sq, self.rank,
                                self.n_iters, seed)
        got = self._gaps(result.fits, result.factors, result.weights, want,
                         coords)
        for n in self.NAMES:
            self.gaps[n] = max(self.gaps[n], got[n])
        if self.ctl is not None:
            ctl = reference.cp_als(self.ctl, self.norm_sq, self.rank,
                                   self.n_iters, seed)
            got = self._gaps(*ctl, want, coords)
            for n in self.NAMES:
                self.control[n] = max(self.control[n], got[n])

    def comparisons(self, limits: dict) -> list[Comparison]:
        return [Comparison(n, self.gaps[n], float(limits[n]))
                for n in self.NAMES]


def _compiles():
    from repro.obs.ledger import LEDGER

    s = LEDGER.stats()
    return s["traces"] if s["traces"] is not None else s["blocks_new"]


def _reset_compiles():
    from repro.obs.ledger import LEDGER

    LEDGER.reset()


def arrival_offsets(law: dict, rate: float, n: int, rng) -> np.ndarray:
    """Offsets from the window's start of ``n`` arrivals at a mean of
    ``rate`` per second, in an order drawn from ``rng``; every seed gets
    the same gaps.

    * ``{"kind": "poisson"}``: the gaps are the n midpoint quantiles of an
      exponential law at ``rate``;
    * ``{"kind": "onoff", "on_s": a, "off_s": b}``: bursts of ``a``
      seconds, with Poisson gaps at ``rate * (a + b) / a`` inside them,
      separated by ``b`` seconds without arrivals.
    """
    kind = law["kind"]
    if kind == "poisson":
        busy_rate, on_s, off_s = rate, math.inf, 0.0
    elif kind == "onoff":
        on_s, off_s = float(law["on_s"]), float(law["off_s"])
        busy_rate = rate * (on_s + off_s) / on_s
    else:
        raise ValueError(f"unknown arrival law {kind!r}")
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / busy_rate)
    busy = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    if math.isinf(on_s):
        return busy
    return busy + np.floor(busy / on_s) * off_s


def pool_nnz(mean_nnz: float, law: dict, per: int) -> list[int]:
    """The nonzero counts of a family's ``per`` pool tensors: the midpoint
    quantiles of a law of sizes relative to the family's ``mean_nnz``.

    * ``{"kind": "uniform", "range": [lo, hi]}``: uniform on
      ``[lo, hi] * mean_nnz``;
    * ``{"kind": "lognormal", "sigma": s, "range": [lo, hi]}``: log-normal
      with median ``mean_nnz`` and shape ``s``, clipped to
      ``[lo, hi] * mean_nnz``: a heavy tail of large requests.
    """
    q = (np.arange(per) + 0.5) / per
    lo, hi = (float(x) for x in law["range"])
    kind = law["kind"]
    if kind == "uniform":
        rel = lo + (hi - lo) * q
    elif kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(x) for x in q])
        rel = np.clip(np.exp(float(law["sigma"]) * z), lo, hi)
    else:
        raise ValueError(f"unknown nnz law {kind!r}")
    return [max(int(round(mean_nnz * r)), 1) for r in rel]


class MultistartFits:
    """Random restarts of CP-ALS on one tensor, back to back, through the
    fused ``repro.core.cpd_als`` with a plan built once in set-up."""

    def __init__(self, config: dict, traffic: dict, seed: int, limits: dict):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.limits = limits
        self.rank = int(config["rank"])
        self.n_iters = int(traffic["n_iters"])
        self.backend = traffic["backend"]
        self.want_control = False
        self.control_gap = None

    def _fit(self, seed: int, n_iters: int):
        from repro.core import cpd_als

        return cpd_als(self.tensor, self.rank, plan=self.plan,
                       n_iters=n_iters, tol=float(self.traffic["tol"]),
                       seed=seed, backend=self.backend,
                       check_every=int(self.traffic["check_every"]),
                       method=self.config["method"])

    def setup(self) -> None:
        from repro.core import SparseTensor, make_plan

        cfg = self.config
        idx, vals, shape = data.powerlaw_sparse(cfg["shape"], cfg["nnz"],
                                                seed=[self.seed, 0])
        self.tensor = SparseTensor(idx, vals, shape)
        self.plan = make_plan(self.tensor, int(cfg["kappa"]))
        self._seeds = np.random.default_rng([self.seed, 1])
        # One iteration runs the same one-sweep executable, mode data
        # upload and fit fetch as every iteration of a timed fit.
        self._fit(seed=0, n_iters=1)

    def window(self, seconds: float, profile) -> Window:
        _reset_compiles()
        self.results = []
        traced_fits = 0
        last_fit_s = 0.0
        t0 = clock()
        offset = (trace_offset(seconds, profile.seconds)
                  if profile is not None else 0.0)
        while clock() - t0 < seconds:
            now = clock()
            # The traced stretch starts and stops on fit boundaries, and
            # at the latest with the last fit that starts in the window.
            if profile is not None and not profile.started and (
                    now - t0 >= offset or now - t0 + last_fit_s >= seconds):
                profile.start()
            fit_seed = int(self._seeds.integers(0, SEED_MAX))
            with _annotation("bench.fit"):
                res = self._fit(seed=fit_seed, n_iters=self.n_iters)
            last_fit_s = clock() - now
            self.results.append((fit_seed, res))
            if profile is not None and profile.active:
                traced_fits += 1
                if clock() - profile.t_start >= profile.seconds:
                    profile.stop()
        t_end = clock()
        if profile is not None and profile.active:
            profile.stop()
        compiles = _compiles()
        iters = sum(r.iters for _, r in self.results)
        syncs = sum(r.host_syncs for _, r in self.results)
        sweep = work.sweep_work(self.tensor.shape, self.tensor.nnz,
                                self.rank)
        return Window(
            e2e={"als_iter_s": (t_end - t0) / max(iters, 1)},
            counters={"host_syncs": syncs, "iters": iters,
                      "compiles": compiles, "fits": len(self.results)},
            attempted=len(self.results),
            failed=sum(not _sound(r, self.n_iters) for _, r in self.results),
            traced_work=sweep.scaled(traced_fits * self.n_iters),
            notes=[f"fits {len(self.results)} iterations {iters} "
                   f"wall_s {t_end - t0:.4f} host_syncs {syncs}"])

    def release(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.plan = None
        gc.collect()

    def check(self, win: Window) -> list[Comparison]:
        rng = np.random.default_rng([self.seed, 2])
        k = min(int(self.traffic["reference_fits"]), len(self.results))
        picks = sorted(rng.choice(len(self.results), size=k, replace=False))
        t = self.tensor
        coords = t.indices[np.sort(rng.choice(
            t.nnz, size=min(t.nnz, MODEL_SAMPLE), replace=False))]
        pair = _ReferencePair(
            lambda p: reference.DeviceKernels(t.indices, t.values, t.shape,
                                              precision=p),
            t.values, self.rank, self.n_iters, self.want_control)
        for i in picks:
            fit_seed, res = self.results[i]
            pair.compare(res, fit_seed, coords)
        if self.want_control:
            self.control_gap = pair.control
        return pair.comparisons(self.limits) + [
            Comparison("unsound_fits", float(win.failed), 0.0)]


class OpenLoop:
    """Requests on a seeded open-loop schedule through
    ``repro.serve.DecompositionService``; each timed from when it was due
    until the client sees its answer."""

    def __init__(self, config: dict, traffic: dict, seed: int, limits: dict):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.limits = limits
        self.rank = int(config["rank"])
        self.n_iters = int(config["n_iters"])
        self.want_control = False
        self.control_gap = None
        self.errors = []

    def setup(self) -> None:
        from repro.core import SparseTensor
        from repro.serve import BucketPolicy, DecompositionService

        cfg = self.config
        per = int(cfg["pool_per_family"])
        law = self.traffic.get("nnz_law") or {
            "kind": "uniform", "range": cfg["nnz_jitter"]}
        self.pool = []
        for f, fam in enumerate(cfg["families"]):
            tensors = []
            for j, nnz in enumerate(pool_nnz(fam["nnz"], law, per)):
                idx, vals, shape = data.powerlaw_sparse(
                    fam["shape"], nnz, seed=[self.seed, 10 + f, j])
                tensors.append(SparseTensor(idx, vals, shape))
            self.pool.append(tensors)
        s = cfg["service"]
        self.svc = DecompositionService(
            self.rank, backend=s["backend"], check_every=s["check_every"],
            policy=BucketPolicy(**s["bucket_policy"]),
            max_batch=s["max_batch"], max_wait_s=s["max_wait_s"],
            double_buffer=s["double_buffer"])
        self._warm_up()

    def _warm_up(self) -> None:
        """Run every (bucket, batch size, window length) executable the
        stream can reach once, through the engine the scheduler drives."""
        eng, policy = self.svc.engine, self.svc.scheduler.policy
        ce = self.svc.engine.check_every
        windows = sorted({min(ce, self.n_iters - it)
                          for it in range(0, self.n_iters, ce)})
        for tensors in self.pool:
            by_cap: dict[int, list] = {}
            for t in tensors:
                by_cap.setdefault(policy.nnz_cap(t.nnz), []).append(t)
            for cap, group in by_cap.items():
                for b in range(1, self.svc.scheduler.max_batch + 1):
                    batch = [group[i % len(group)] for i in range(b)]
                    for k in windows:
                        eng.decompose_batch(batch, n_iters=k, tol=-1.0,
                                            seeds=list(range(b)),
                                            nnz_cap=cap)

    def schedule(self, seconds: float):
        """Arrival offsets, (family, pool index) and init seed of every
        request due in the window.  Every seed gets the same number of
        requests, the same gaps and the same pool sizes, in its own
        order."""
        rate = float(self.traffic["rate_per_s"])
        rng = np.random.default_rng([self.seed, 3])
        n = max(int(round(rate * seconds)), 1)
        shares = np.array([f["share"] for f in self.config["families"]],
                          np.float64)
        counts = np.floor(shares / shares.sum() * n).astype(int)
        counts[: n - counts.sum()] += 1
        per = int(self.config["pool_per_family"])
        reqs = [(f, j % per) for f, c in enumerate(counts) for j in range(c)]
        reqs = [reqs[i] for i in rng.permutation(n)]
        arrivals = arrival_offsets(self.traffic["arrival_law"], rate, n, rng)
        seeds = rng.integers(0, SEED_MAX, size=n)
        keep = arrivals < seconds
        return (arrivals[keep], [r for r, k in zip(reqs, keep) if k],
                [int(s) for s, k in zip(seeds, keep) if k])

    def window(self, seconds: float, profile) -> Window:
        svc, metrics = self.svc, self.svc.metrics
        arrivals, reqs, seeds = self.schedule(seconds)
        self.reqs, self.seeds = reqs, seeds
        n = len(reqs)
        drain_s = float(self.traffic["drain_s"])
        fut = [None] * n
        done_at = [None] * n
        late = np.zeros(n)
        traced = []
        pending: dict[int, object] = {}
        _reset_compiles()
        t0 = clock()
        close, deadline = t0 + seconds, t0 + seconds + drain_s
        due = t0 + arrivals
        last_batches, last_sweep = metrics.batch_count, t0
        offset = (trace_offset(seconds, profile.seconds)
                  if profile is not None else 0.0)

        def sweep(now):
            # Without double buffering a batch runs inside the submit or
            # poll call that flushes it, so a future resolved while the
            # profile ran belongs to a batch that ran wholly inside it.
            for k in [k for k, f in pending.items() if f.done()]:
                done_at[k] = now
                del pending[k]
                if profile is not None and profile.active:
                    traced.append(k)

        at_close = None
        i = 0
        while i < n or pending:
            now = clock()
            # The traced stretch starts and stops between calls into the
            # service, which are batch boundaries.
            if profile is not None and not profile.started and (
                    now - t0 >= offset):
                sweep(now)
                profile.start()
            elif profile is not None and profile.active and (
                    now - profile.t_start >= profile.seconds):
                sweep(now)
                profile.stop()
            if at_close is None and now >= close:
                at_close = self._service_counters()
            if i == n and now >= deadline:
                break
            if i < n and now >= due[i]:
                late[i] = now - due[i]
                tf, tj = reqs[i]
                with _annotation("bench.submit"):
                    fut[i] = svc.submit(self.pool[tf][tj],
                                        n_iters=self.n_iters, tol=-1.0,
                                        seed=seeds[i])
                pending[i] = fut[i]
                i += 1
            else:
                with _annotation("bench.poll"):
                    flushed = svc.poll()
                if not flushed:
                    nxt = due[i] if i < n else now + 5e-4
                    pause = min(nxt - clock(), 1e-3)
                    if pause > 0:
                        time.sleep(pause)
            now = clock()
            if metrics.batch_count != last_batches or now - last_sweep > 0.05:
                last_batches, last_sweep = metrics.batch_count, now
                sweep(now)
        if profile is not None and profile.active:
            sweep(clock())
            profile.stop()
        if at_close is None:
            at_close = self._service_counters()
        compiles = _compiles()
        self.results = [None] * n
        failed = 0
        lat = np.empty(n)
        for k in range(n):
            res = None
            if done_at[k] is not None:
                try:
                    with _annotation("bench.result"):
                        res = fut[k].result()
                except Exception as exc:          # the request failed
                    self.errors.append(repr(exc))
            if res is None or not _sound(res, self.n_iters):
                failed += 1
                lat[k] = deadline - due[k]        # missed any limit
            else:
                lat[k] = done_at[k] - due[k]
                self.results[k] = res
        in_window = sum(1 for t in done_at if t is not None and t <= close)
        p50, p95, p99 = np.percentile(lat, [50, 95, 99]) * 1e3
        traced_work = work.ZERO
        for k in traced:
            t = self.pool[reqs[k][0]][reqs[k][1]]
            traced_work = traced_work + work.sweep_work(
                t.shape, t.nnz, self.rank).scaled(self.n_iters)
        return Window(
            e2e={"latency_p95_ms": float(p95),
                 "decomp_per_s": in_window / seconds},
            counters={"compiles": compiles, **at_close},
            attempted=n, failed=failed, traced_work=traced_work,
            notes=[f"requests {n} completed_in_window {in_window} "
                   f"failed {failed} unresolved_at_close "
                   f"{sum(1 for t in done_at if t is None or t > close)}",
                   f"latency_ms p50 {p50:.3f} p95 {p95:.3f} p99 {p99:.3f}",
                   f"generator_late_ms p50 {np.median(late) * 1e3:.3f} "
                   f"p99 {np.percentile(late, 99) * 1e3:.3f} "
                   f"max {late.max() * 1e3:.3f}"])

    def _service_counters(self) -> dict:
        snap = self.svc.snapshot()
        d = snap["dispatch"]
        return {"completed": snap["completed"], "batches": snap["batches"],
                "padding_overhead": snap["padding_overhead"],
                "dispatches": d["count"], "assembly_s": d["assembly_s"]}

    def release(self) -> None:
        self.svc = None
        gc.collect()

    def check(self, win: Window) -> list[Comparison]:
        ok = [k for k, r in enumerate(self.results) if r is not None]
        rng = np.random.default_rng([self.seed, 4])
        want = min(int(self.traffic["reference_requests"]), len(ok))
        picks = []
        if ok:
            nnz = [self.pool[self.reqs[k][0]][self.reqs[k][1]].nnz
                   for k in ok]
            longest = ok[int(np.argmax(nnz))]
            rest = [k for k in ok if k != longest]
            picks = [longest] + [int(k) for k in rng.choice(
                rest, size=want - 1, replace=False)]
        gaps = {"fit_gap": 0.0, "model_gap": 0.0}
        control = {"fit_gap": 0.0, "model_gap": 0.0}
        for k in picks:
            t = self.pool[self.reqs[k][0]][self.reqs[k][1]]
            pair = _ReferencePair(
                lambda p: reference.HostKernels(t.indices, t.values, t.shape,
                                                precision=p),
                t.values, self.rank, self.n_iters, self.want_control)
            pair.compare(self.results[k], self.seeds[k], t.indices)
            for name in gaps:
                gaps[name] = max(gaps[name], pair.gaps[name])
                if self.want_control:
                    control[name] = max(control[name], pair.control[name])
        if self.want_control:
            self.control_gap = control
        return [Comparison(n, v, float(self.limits[n]))
                for n, v in gaps.items()] + [
            Comparison("failed_requests", float(win.failed), 0.0)]


LOADS = {"multistart_fits": MultistartFits, "open_loop": OpenLoop}
