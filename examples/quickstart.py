"""Quickstart: one text-to-image request through micro-serving.

Composes the SD3-family workflow with the Python DSL, registers it, and
really executes it (tiny-scale models) on the host device through the
full LegoDiffusion stack: compiler -> scheduler -> executors -> data
engine.  Saves the generated image as examples/quickstart_image.npy
(next to this script, regardless of the working directory).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

import numpy as np

from repro.core import LocalBackend, ServingSystem, use_compile_cache
from repro.diffusion import make_basic_workflow

use_compile_cache()
system = ServingSystem(n_executors=2, backend=LocalBackend())
workflow = make_basic_workflow("sd3")
system.register(workflow)

request = system.submit(
    "sd3:basic",
    inputs={"seed": 42, "prompt": "a watercolor fox in a snowy forest"},
    steps=8,            # static input: unrolls 8 denoising iterations
)
system.run()

image_key = request.ref_key(request.graph.outputs["image"])
image = np.asarray(system.coordinator.engine.value_of(image_key))
out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "quickstart_image.npy")
np.save(out_path, image)

c = system.coordinator
print(f"status: {request.status}  nodes executed: {len(c.dispatch_log)}")
print(f"image: {image.shape}, range [{image.min():.3f}, {image.max():.3f}]")
print(f"data engine: {c.engine.num_transfers} transfers, "
      f"{c.engine.bytes_transferred/2**20:.1f} MiB moved")
print(f"saved {os.path.relpath(out_path)}")
