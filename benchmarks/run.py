"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` trims the heavy rate
sweeps; ``--only <module>`` runs a single benchmark.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (
    bench_adaptive,
    bench_admission,
    bench_async_lora,
    bench_burst,
    bench_caching,
    bench_chaos,
    bench_datafetch,
    bench_latency_throughput,
    bench_multitenant,
    bench_overhead,
    bench_parallelism,
    bench_proc_chaos,
    bench_programmability,
    bench_rawspeed,
    bench_scaling,
    bench_sharing,
    bench_slo_scale,
    bench_slo_vs_rate,
    bench_telemetry,
    bench_testbed,
    roofline,
)
from repro.core.compile_cache import use_compile_cache

ALL = [
    ("fig3_scaling", bench_scaling),
    ("fig3_latency_throughput", bench_latency_throughput),
    ("fig4_sharing", bench_sharing),
    ("fig4_adaptive", bench_adaptive),
    ("fig9_rate", bench_slo_vs_rate),
    ("fig9g_slo_scale", bench_slo_scale),
    ("fig9h_burst", bench_burst),
    ("fig9i_testbed", bench_testbed),
    ("fig10_parallelism", bench_parallelism),
    ("fig10_admission", bench_admission),
    ("fig11_datafetch", bench_datafetch),
    ("table3_programmability", bench_programmability),
    ("s74_caching", bench_caching),
    ("s74_async_lora", bench_async_lora),
    ("s75_overhead", bench_overhead),
    ("s6_chaos", bench_chaos),
    ("s7_proc_chaos", bench_proc_chaos),
    ("multitenant", bench_multitenant),
    ("s8_telemetry", bench_telemetry),
    ("s9_rawspeed", bench_rawspeed),
    ("roofline", roofline),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in ALL:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            if args.quick and name == "fig9_rate":
                mod.run(settings=("s1", "s6"), rates=(1.0, 2.0))
            elif args.quick and name in ("multitenant", "s8_telemetry",
                                         "s9_rawspeed"):
                mod.run(smoke=True)
            else:
                mod.run()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
