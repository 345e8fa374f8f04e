"""Cross-workflow model sharing with per-request LoRAs (§5.1, §7.3).

Three workflows share ONE SDXL backbone replica pool: a plain workflow
and two LoRA-styled variants.  The scheduler batches same-model nodes
across workflows, hot-swaps adapters (Katz-style async loading), and the
model-state table keeps L_load at zero for warm replicas.

Run:  PYTHONPATH=src python examples/multi_lora_sharing.py
"""

from repro.core import ServingSystem, use_compile_cache
from repro.diffusion import make_basic_workflow, make_lora_workflow
from repro.sim import generate_trace

use_compile_cache()

system = ServingSystem(n_executors=4, admission_enabled=False)
wfs = {}
for t in (make_basic_workflow("sdxl"),
          make_lora_workflow("sdxl", "papercut"),
          make_lora_workflow("sdxl", "yarn-art")):
    system.register(t)
    wfs[t.name] = t

trace = generate_trace(list(wfs), rate=0.8, duration=120, cv=1.5, seed=1)
for t in trace:
    system.submit(t.workflow, inputs=t.inputs, arrival=t.arrival)
system.run()

c = system.coordinator
shared_batches = sum(
    1 for d in c.dispatch_log
    if len({rn.request.workflow_name for rn in d.nodes}) > 1)
loads = sum(e.models_loaded_count for e in system.executors)
distinct = {m for e in system.executors for m in e.loaded}
print(f"requests served: {len(c.finished)}  mean latency {system.mean_latency():.2f}s")
print(f"dispatches: {len(c.dispatch_log)}  cross-workflow batches: {shared_batches}")
print(f"model loads: {loads}  distinct resident models: {len(distinct)}")
print(f"adapter swaps priced into schedule: "
      f"{sum(1 for d in c.dispatch_log if d.patch_swap > 0)}")
