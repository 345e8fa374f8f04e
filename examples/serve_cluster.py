"""End-to-end serving driver (the paper's kind of workload).

Replays a bursty production-style trace of mixed Flux workflows (S6:
basic / +ControlNet x2 for Flux-Schnell and Flux-Dev) against a simulated
16-GPU cluster, serving with LegoDiffusion micro-serving AND the three
monolithic baselines, and prints the Fig-9-style comparison.

Run:  PYTHONPATH=src python examples/serve_cluster.py [--rate 1.0]
"""

import argparse

from repro.core import ProfileStore, ServingSystem, use_compile_cache
from repro.core.profiles import GPU_H800
from repro.diffusion import table2_setting
from repro.sim import MonolithicSystem, WorkflowSpec, generate_trace

ap = argparse.ArgumentParser()
ap.add_argument("--rate", type=float, default=1.0)
ap.add_argument("--gpus", type=int, default=16)
ap.add_argument("--duration", type=float, default=240.0)
ap.add_argument("--cv", type=float, default=2.0)
args = ap.parse_args()
use_compile_cache()

wfs = table2_setting("s6")
trace = generate_trace(list(wfs), rate=args.rate, duration=args.duration,
                       cv=args.cv, seed=0)
print(f"trace: {len(trace)} requests over {args.duration:.0f}s "
      f"(rate {args.rate}/s, CV {args.cv}), {args.gpus} GPUs\n")

# --- LegoDiffusion micro-serving
lego = ServingSystem(n_executors=args.gpus, admission_enabled=True)
for t in wfs.values():
    lego.register(t)
solo = {n: lego.solo_latency(n) for n in wfs}
for t in trace:
    lego.submit(t.workflow, inputs=t.inputs, arrival=t.arrival,
                slo_seconds=2.0 * solo[t.workflow])
lego.run()
print(f"LegoDiffusion : SLO attainment {lego.slo_attainment():5.1%}  "
      f"mean latency {lego.mean_latency():6.2f}s  "
      f"rejected {len(lego.coordinator.rejected)}")

# --- monolithic baselines
profiles = ProfileStore(GPU_H800)
reg = ServingSystem(n_executors=1)
for t in wfs.values():
    reg.register(t)
specs = {n: WorkflowSpec.from_graph(reg.registry.instantiate(n), profiles)
         for n in wfs}
for mode in ("diffusers", "diffusers-c", "diffusers-s"):
    m = MonolithicSystem(args.gpus, profiles, specs, mode=mode)
    for t in trace:
        m.submit(t.arrival, t.workflow, 2.0 * specs[t.workflow].serial_seconds_b1)
    m.run()
    print(f"{mode:14s}: SLO attainment {m.slo_attainment():5.1%}  "
          f"loads {m.total_loads()}")
