"""Serving launcher: run the micro-serving system on a workload.

``--plane sim`` replays a trace through the cluster simulator (the paper's
evaluation mode); ``--plane local`` really executes tiny diffusion models
on the host device through the same coordinator.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--setting", default="s1",
                    choices=["s1", "s2", "s3", "s4", "s5", "s6"])
    ap.add_argument("--plane", default="sim", choices=["sim", "local"])
    ap.add_argument("--executors", type=int, default=8)
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--cv", type=float, default=2.0)
    ap.add_argument("--slo-scale", type=float, default=2.0)
    ap.add_argument("--no-admission", action="store_true")
    args = ap.parse_args()

    from repro.core import LocalBackend, ServingSystem, use_compile_cache
    from repro.diffusion import table2_setting
    from repro.sim import generate_trace

    use_compile_cache()
    wfs = table2_setting(args.setting)
    backend = LocalBackend() if args.plane == "local" else None
    sys_ = ServingSystem(n_executors=args.executors,
                         admission_enabled=not args.no_admission,
                         backend=backend)
    for t in wfs.values():
        sys_.register(t)
    solo = {n: sys_.solo_latency(n) for n in wfs}
    trace = generate_trace(list(wfs), rate=args.rate, duration=args.duration,
                           cv=args.cv, seed=0)
    kw = {"steps": 3} if args.plane == "local" else {}
    for t in trace[: (8 if args.plane == "local" else None)]:
        sys_.submit(t.workflow, inputs=t.inputs, arrival=t.arrival,
                    slo_seconds=args.slo_scale * solo[t.workflow], **kw)
    sys_.run()
    c = sys_.coordinator
    print(f"requests: {len(c.finished)} done, {len(c.rejected)} rejected")
    print(f"SLO attainment: {sys_.slo_attainment():.3f}")
    print(f"mean latency: {sys_.mean_latency():.3f}s  p99: {c.p99_latency():.3f}s")
    print(f"dispatches: {len(c.dispatch_log)}  "
          f"transfers: {c.engine.num_transfers} "
          f"({c.engine.bytes_transferred/2**30:.2f} GiB)")


if __name__ == "__main__":
    main()
