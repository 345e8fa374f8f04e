"""Fig 10-left: intra-node (latent) and inter-node (ControlNet deferred
fetch) parallelism speedups — plus the MEASURED sharded-execution study.

Two arms:

* **analytic** (`fig10_*`) — the paper-comparable speedup readouts from
  the latency profiles, unchanged;
* **measured** (`sharded_*`) — real stacked backbone forwards on a
  k-device submesh via :class:`ShardedBackend` at k = 1/2/4, emitting
  ``BENCH_parallelism.json`` with per-k throughput.  Waves of W requests
  are served per trial; arms are jit-warmed up front and trials
  interleave round-robin so host-noise bursts hit every k alike; each
  arm reports its MEDIAN wave time (robust to slow and lucky-fast
  outliers).  On hosts with fewer than 4 devices the study re-executes
  itself in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the same
  virtual-device mechanism the mesh parity tests use); on real TPU/GPU
  meshes it runs in-process against the hardware.

  The study runs on the reference attention path (see
  ``bench_overhead.batched_exec_study`` for the rationale: on CPU the
  Pallas kernel's interpret-mode emulation cost would swamp the sharding
  signal being measured).
"""

from __future__ import annotations

import os
import statistics
import subprocess
import sys
import time

from benchmarks.common import emit, run_lego_trace
from benchmarks.emit import write_bench_json
from repro.core import ProfileStore, Scheduler
from repro.core.profiles import GPU_H800
from repro.diffusion import FAMILIES, ModelSet, make_controlnet_workflow
from repro.diffusion.serving import DiffusionBackbone
from repro.sim import generate_trace

PARALLELISM_JSON = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_parallelism.json")


def analytic_study() -> None:
    profiles = ProfileStore(GPU_H800)
    for fam in ("sd3", "sd3.5-large", "flux-schnell", "flux-dev"):
        ms = ModelSet(FAMILIES[fam])
        p = profiles.profile_model(ms.backbone)
        sp = p.speedup(1, 2)
        emit(f"fig10_intra_node[{fam}]", p.infer_time(1, 2) * 1e6,
             f"speedup={sp:.2f}x")
    # inter-node: deferred vs eager ControlNet residuals (2 executors)
    for fam in ("sd3", "flux-dev"):
        lats = {}
        for tag, eager in (("deferred", False), ("eager", True)):
            ms = ModelSet(FAMILIES[fam])
            ms.backbone = DiffusionBackbone(FAMILIES[fam], eager_controlnet=eager)
            wf = make_controlnet_workflow(fam, 1, ms)
            trace = generate_trace([wf.name], rate=0.05, duration=200, cv=1.0,
                                   seed=23)
            # cap intra-node parallelism so the ablation isolates the
            # inter-node (deferred-fetch) mechanism; see EXPERIMENTS.md for
            # the eager+latent-parallel interaction we found
            sys_ = run_lego_trace({wf.name: wf}, trace, 2, slo_scale=None,
                                  admission=False,
                                  scheduler_kwargs={"max_parallelism_cap": 1})
            lats[tag] = sys_.mean_latency()
        emit(f"fig10_inter_node[{fam}]", lats["deferred"] * 1e6,
             f"speedup={lats['eager']/lats['deferred']:.2f}x")


class _ShardArm:
    """One (k) arm: a warm ShardedBackend serving W-request backbone waves
    on a k-device submesh (k=1 runs the plain single-device path)."""

    def __init__(self, k: int, wave: int, backbone, cfg) -> None:
        import jax
        from repro.core import MeshManager, ShardedBackend

        self.k = k
        self.wave = wave
        self.backend = ShardedBackend(MeshManager())
        self.mesh = (self.backend.mesh_manager.submesh(list(range(k)))
                     if k > 1 else None)
        key = jax.random.PRNGKey(11)
        ks = jax.random.split(key, 2 * wave)
        self.kwargs = [{
            "latents": jax.random.normal(
                ks[2 * i], (1, cfg.latent_size, cfg.latent_size,
                            cfg.latent_channels)),
            "prompt_embeds": jax.random.normal(
                ks[2 * i + 1], (1, cfg.text_tokens, cfg.text_dim)),
            "t": 0.4, "guidance": 4.5,
        } for i in range(wave)]
        self.backbone = backbone
        self.waves = []
        self.run_trial()          # jit warm-up (excluded from the medians)
        self.waves.clear()

    def run_trial(self) -> None:
        t0 = time.perf_counter()
        if self.mesh is not None:
            outs, _, _ = self.backend.execute_batch(
                self.backbone, [dict(kw) for kw in self.kwargs],
                mesh=self.mesh)
        else:
            outs, _, _ = self.backend.execute_batch(
                self.backbone, [dict(kw) for kw in self.kwargs])
        self.waves.append(time.perf_counter() - t0)

    @property
    def wave_seconds(self) -> float:
        return statistics.median(self.waves)


def sharded_study(trials: int = 15, wave: int = 8) -> None:
    """Measured sharded-vs-single-device backbone throughput at k=1/2/4."""
    import dataclasses

    import jax

    if jax.device_count() < 4:
        if jax.default_backend() != "cpu":
            # the chip belongs to this process: a child could not reach it
            emit("sharded_backbone_skipped", 0.0,
                 f"{jax.device_count()} {jax.default_backend()} device(s); "
                 f"the study needs 4")
            return
        _respawn_sharded_study(trials, wave)
        return
    from repro.nn.layers import set_flash_attention

    # bench-scale architecture: the tier-1 toy backbone finishes a wave in
    # ~2 ms, where per-device dispatch (not compute) decides the ranking;
    # scaling d_model/layers/grid up puts a wave in the hundreds-of-ms
    # regime the sharding is for, while still loading in seconds on CPU
    fam = dataclasses.replace(
        FAMILIES["sd3"],
        dit=dataclasses.replace(FAMILIES["sd3"].dit, d_model=256, n_layers=6,
                                n_heads=8, d_ff=1024, latent_size=32))
    backbone = DiffusionBackbone(fam)
    ks = (1, 2, 4)
    prev_flash = set_flash_attention(False)
    try:
        arms = {k: _ShardArm(k, wave, backbone, fam.dit) for k in ks}
        for _ in range(trials):
            for k in ks:
                arms[k].run_trial()
    finally:
        set_flash_attention(prev_flash)
    rows = []
    for k in ks:
        arm = arms[k]
        rows.append({
            "k": k,
            "wave_seconds": arm.wave_seconds,
            "images_per_s": wave / arm.wave_seconds,
            "speedup_vs_single": arms[1].wave_seconds / arm.wave_seconds,
            "sharded_forwards": len(arm.backend.shard_log),
            "devices": sorted({d for s in arm.backend.shard_log
                               for d in s[3]}),
        })
    for row in rows:
        emit(f"sharded_backbone_k{row['k']}",
             1e6 * row["wave_seconds"] / wave,
             f"{row['images_per_s']:.2f} img/s "
             f"({row['speedup_vs_single']:.2f}x vs k=1, "
             f"devices={row['devices']})")
    mono = all(rows[i + 1]["images_per_s"] >= rows[i]["images_per_s"]
               for i in range(len(rows) - 1))
    write_bench_json("parallelism", rows, path=PARALLELISM_JSON,
                     gates={"throughput_monotone": mono})
    emit("sharded_backbone_monotone", float(mono),
         f"throughput monotone k=1..4: {mono}; wrote {PARALLELISM_JSON}")


def _respawn_sharded_study(trials: int, wave: int) -> None:
    """Too few local CPU devices: rerun this study in a CPU child with 8
    forced virtual host devices (results land in the same JSON/CSV
    stream)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(root, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    code = ("from benchmarks.bench_parallelism import sharded_study; "
            f"sharded_study(trials={trials}, wave={wave})")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=1800)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        emit("sharded_backbone_error", 0.0, out.stderr[-400:].replace("\n", ";"))


def run() -> None:
    analytic_study()
    sharded_study()
