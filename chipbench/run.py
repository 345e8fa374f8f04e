#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic
mix, limits and metrics are found by name through ``BENCHMARK.json``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result, one JSON object;
the last lines of standard error are the numbers compared, each beside
its limit.  Off a TPU, on too few chips or on a chip with no published
peaks the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def end_to_end(cell, out) -> dict:
    values = {"setup_s": out.setup_s}
    seg = [d for d in out.dispatches if d.model_id.startswith("segment:")]
    if seg:
        values["steps_per_s"] = (sum(d.batch_size * d.steps for d in seg)
                                 / out.window_s)
    if out.latencies:
        lat = sorted(out.latencies)
        values["latency_p50_s"] = statistics.median(lat)
        # the nearest-rank 95th percentile
        values["latency_p95_s"] = lat[max(0, -(-95 * len(lat) // 100) - 1)]
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def per_layer(cell, out, harness) -> dict:
    import jax

    from chipbench.peaks import peaks_for

    arch = harness.architecture(cell.config)
    readings = harness.Readings(
        window_s=out.traced_s, dispatches=out.traced, trace=out.trace,
        geometry=arch.geometry_from_config(cell.config), architecture=arch,
        peaks=peaks_for(jax.devices()[0].device_kind),
        programs=harness.PROGRAMS, flash_kernel=harness.FLASH_KERNEL)
    metrics = {}
    for m in cell.per_layer:
        v = harness.read_metric(m["name"], readings)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness, xplane

    cell = harness.resolve(args.workload)
    try:
        dev = harness.check_device(cell.chips)
    except harness.NoChip as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 2
    import jax

    harness.use_cache()
    harness.log(f"device: {dev.device_kind} x {jax.device_count()}; "
                f"cell {cell.name}, seed {args.seed}, {args.seconds} s")
    try:
        out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                               T_PROCESS)
    except harness.RunFailure as e:
        print(f"run failed: {e}", file=sys.stderr)
        return 3
    ok = harness.correct(out.checks, cell.limits)
    result = {
        "correct": ok,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": (per_layer(cell, out, harness) if args.trace
                    else end_to_end(cell, out)),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": out.memory_peak_bytes},
    }
    if args.trace:
        busy = out.trace.busy_s
        result["device"].update(busy_s=busy, window_s=out.traced_s)
        d = out.trace.devices[0]
        result["breakdown"] = {
            "device_ops": xplane.top_ops(d),
            "idle_gaps": xplane.named_gaps(out.trace, d)}
    result["checks"] = {k: {"value": v, "limit": cell.limits.get(k)}
                        for k, v in out.checks.items()}
    harness.log(f"window {out.window_s:.3f} s, {len(out.dispatches)} "
                f"dispatches, latencies {out.latencies}, reference check "
                f"{out.check_seconds:.3f} s")
    for k, v in out.checks.items():
        harness.log(f"check {k} {v:.6e} limit {cell.limits.get(k)}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
