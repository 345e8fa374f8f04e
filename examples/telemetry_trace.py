"""Telemetry demo: a traced serving run exported as a Perfetto trace.

Forces tracing on (no env var needed), runs a short workload, writes a
Chrome trace-event JSON you can open at https://ui.perfetto.dev, prints
the Prometheus metrics dump, and self-validates the export.

Two modes:

* default — a simulated S1 trace on 8 executors (coordinator-only
  tracks: requests, control, one per executor);
* ``--proc`` — a real process-isolated run (two worker processes behind
  the frame transport): worker stage/forward spans stitch into the
  coordinator's trace across the wire, and request flows span pids.

Run:  PYTHONPATH=src:. python examples/telemetry_trace.py [--proc]
                        [--out trace.json]

CI runs both modes and gates on the validation (`--expect-multi-pid`
for the proc trace).
"""

import argparse

from repro.core import ServingSystem, use_compile_cache
from repro.core.telemetry import configure, validate_chrome_trace


def run_sim(out: str) -> None:
    from benchmarks.common import run_lego_trace
    from repro.diffusion import table2_setting
    from repro.sim import generate_trace

    wfs = table2_setting("s1")
    trace = generate_trace(list(wfs), rate=1.0, duration=20.0, cv=1.0,
                           seed=3)
    sys_ = run_lego_trace(wfs, trace, 8, slo_scale=3.0)
    sys_.export_trace(out)
    print(sys_.metrics_text())
    stats = validate_chrome_trace(out)
    print(f"wrote {out}: {stats}")


def run_proc(out: str) -> None:
    from repro.core import ProcBackend, ProcConfig, Scheduler
    from repro.diffusion import make_basic_workflow

    cfg = ProcConfig(hb_interval=0.02, hb_timeout=2.0, spawn_timeout=120.0)
    sys_ = ServingSystem(n_executors=2, backend=ProcBackend(cfg))
    sys_.coordinator.scheduler = Scheduler(
        sys_.profiles, use_declared_max_batch=True, segment_chunk=2)
    wf = make_basic_workflow("sd3")
    sys_.register(wf)
    with sys_:
        req = sys_.submit(wf.name, inputs={"seed": 0, "prompt": "a fox"},
                          arrival=0.0, steps=5)
        sys_.run()
    assert req.status == "done", req.status
    sys_.export_trace(out)
    print(sys_.metrics_text())
    stats = validate_chrome_trace(out, expect_multi_pid=True)
    print(f"wrote {out}: {stats}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--proc", action="store_true",
                    help="process-isolated plane (worker spans stitch "
                         "across pids)")
    ap.add_argument("--out", default="trace.json")
    args = ap.parse_args()
    use_compile_cache()
    configure(True)
    if args.proc:
        run_proc(args.out)
    else:
        run_sim(args.out)


if __name__ == "__main__":
    main()
