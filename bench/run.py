"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's configuration, traffic and
limits are found by name from ``BENCHMARK.json`` (see ``bench.harness``).
The run generates its data from ``--seed``, warms up every executable its
window uses (set-up, reported as ``setup_s``), measures for ``--seconds``,
then checks what the timed path returned against the plain reference.
With ``--trace 1`` the first part of the window runs under the JAX
profiler and the line carries the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit;
the same numbers are the last lines of standard error.  The run exits
non-zero, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or a device kind missing from ``bench/peaks.json``.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _finite(obj):
    """Non-finite floats as null, so that the line is strict JSON."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the system under test is missing: no {ROOT}/src/repro")
    from bench import harness, work

    try:
        cell = harness.resolve(harness.load_benchmark(ROOT), args.workload)
    except (KeyError, FileNotFoundError) as exc:
        return fail(str(exc))
    devices = harness.start_jax()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        return fail(f"{args.workload} needs {chips} chips, JAX sees "
                    f"{len(devices)}")
    dev = devices[0]
    try:
        peaks = work.load_peaks(dev.device_kind)
    except KeyError as exc:
        return fail(str(exc))

    def peak_bytes() -> int:
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "peak_bytes": peak_bytes}
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           peaks, device, T_START)
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
