"""Readings for a cell's limits, on the chip: the program and the control,
seed after seed, in one process.

    python bench/calibrate.py --workload chicago-als.segment --seconds 5 \
        --seeds 11 12 13 ...

For each seed this sets the cell up, runs a window of ``--seconds`` at the
cell's own load, and compares what the timed path returned with the plain
reference, as a run does; unless ``--no-control``, it also computes the
precision control (the reference in bfloat16) against the same reference.
Each line gives the seed, every number compared, and the control's.  A
limit lies above the largest of the program's numbers and below the
smallest of the control's (``bench/limits/<workload>.json`` records both
readings).

``--program-precision high|default`` reads the program itself at a lower
matmul precision: its sweeps are traced under ``high`` (three bfloat16
passes) or ``default`` (one bfloat16 pass on a TPU) instead of
``float32``, the step a later change could take for speed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
# --program-precision -> jax.default_matmul_precision for the sweeps.
LOWERED = {"high": "high", "default": "default"}


def lower_program_precision(precision: str) -> None:
    """Trace the program's sweeps at ``precision`` instead of float32:
    swap the wrapper that ``repro.core.als_device`` applies to each sweep
    it builds.  Call before any sweep is built."""
    import functools

    import jax
    from repro.core import als_device

    def lowered(sweep):
        @functools.wraps(sweep)
        def run(*args):
            with jax.default_matmul_precision(precision):
                return sweep(*args)
        return run

    als_device._f32_matmuls = lowered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-precision", default="float32",
                    choices=sorted(LOWERED) + ["float32"])
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness, work

    dev = harness.start_jax()[0]
    if dev.platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    if args.program_precision != "float32":
        lower_program_precision(LOWERED[args.program_precision])
    cell = harness.resolve(harness.load_benchmark(ROOT, staged=True), args.workload)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": 1,
              "peak_bytes": lambda: 0}
    peaks = work.load_peaks(dev.device_kind)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, peaks,
                               device, time.perf_counter(),
                               control=not args.no_control,
                               log=lambda msg: None)
        row = {"seed": seed, "program_precision": args.program_precision,
               "correct": out["correct"],
               "program": {k: v["value"] for k, v in out["checks"].items()},
               "control": out.get("control")}
        print("calibrate " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
