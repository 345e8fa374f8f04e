#!/usr/bin/env python3
"""The readings a cell's limits are set from, many seeds in one process.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s> [--quant int8]

Each seed runs the cell's set-up traffic, a window of ``--seconds`` and
the check, as ``run.py`` does, on one loaded program: the models load
(and compile) once.  The control of a bfloat16 configuration, which the
limits must fail, is ``--quant fp8`` (or ``int8``): the program's own
quantized path (``REPRO_QUANT``); or, where that path does not fit the
chip, ``--control``: the reference with float8 weights put in the
program's place on the inputs of the window.  One JSON line per seed:
the numbers compared and the end-to-end readings of that window.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--quant", choices=("off", "int8", "fp8"), default="off")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    from chipbench import harness

    cell = harness.resolve(args.workload)
    try:
        harness.check_device(cell.chips)
    except harness.NoChip as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 2
    from repro.core import LocalBackend
    from repro.nn.layers import set_quant_mode

    harness.use_cache()
    set_quant_mode(args.quant)
    backend = LocalBackend()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0,
                               backend=backend, control=args.control)
        steps = sum(d.batch_size * d.steps for d in out.dispatches
                    if d.model_id.startswith("segment:"))
        print(json.dumps({
            "workload": cell.name, "seed": seed, "quant": args.quant,
            "control": args.control,
            "checks": out.checks,
            "correct": harness.correct(out.checks, cell.limits),
            "steps_per_s": steps / out.window_s,
            "latency_p50_s": (statistics.median(out.latencies)
                              if out.latencies else None),
            "setup_s": out.setup_s, "check_s": out.check_seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
