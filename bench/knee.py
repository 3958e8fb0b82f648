"""Sweep the open-loop rate of a service cell to find its knee, on the chip.

    python bench/knee.py --workload windows-steady --seed 7 --seconds 20 \
        --rates 2 4 6 8 10 12

One process sets the cell up once and runs one window per rate, each with
the cell's own schedule at that rate.  For each rate it prints the p50,
p95 and p99 latency from each request's scheduled arrival, the requests
completed in the window per second, and the backlog (requests due but not
answered) when the window closed.  The knee is the highest rate whose p95
stays within the configuration's ``latency_limit_ms`` while the backlog
at the close stays within one full batch.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    if harness.start_jax()[0].platform != "tpu":
        print("knee.py: no TPU", file=sys.stderr)
        return 2
    cell = harness.resolve(harness.load_benchmark(ROOT, staged=True), args.workload)
    drv = harness.make_load(cell, args.seed)
    drv.setup()
    limit = float(cell.config["latency_limit_ms"])
    for rate in args.rates:
        drv.traffic = dict(drv.traffic, rate_per_s=rate)
        win = drv.window(args.seconds, None)
        row = {"rate_per_s": rate, "limit_ms": limit,
               "decomp_per_s": win.e2e["decomp_per_s"],
               "latency_p95_ms": win.e2e["latency_p95_ms"],
               "failed": win.failed, "notes": win.notes}
        print("knee " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
