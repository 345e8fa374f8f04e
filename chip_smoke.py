#!/usr/bin/env python3
"""Smoke check of the served path on a TPU: sd3 at its published backbone
width, random weights from a seed.

With no arguments (one chip) it serves ``sd3:basic`` through the normal
entry points — ``ModelSet`` -> ``make_basic_workflow`` ->
``ServingSystem(backend=LocalBackend())`` -> ``submit``/``run`` — with the
published MMDiT backbone (d_model 1536, 24 layers, 24 heads, 128x128x16
latents, 333 text tokens, bf16) at 28 steps with classifier-free guidance.
It checks that every request ends done with a finite 1024x1024x3 image,
that the compiled backbone step holds the Pallas kernel
(``tpu_custom_call``), that one backbone step agrees with the same step on
the reference attention at highest matmul precision, and that the flash
kernel alone agrees with the reference at the joint sequence (4429
tokens, a padded last block), at head_dim 64 and at 128.

``--chips 4`` runs only adaptive model parallelism: a published-width
``DenoiseSegment`` batch dispatched at k=4 through ``ShardedBackend``, with
CFG rows sharded (B=2, 4 rows) and sequence-sharded (B=1, 2 rows), each
compared with the k=1 result on one chip, at the served guidance and at
0.5.

Each phase that fails exits non-zero; the last stdout line of a pass is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It refuses to run off a TPU, and when an environment switch forces a
reference path in place of a kernel.

Run from the repository root:  python3 chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 4          # one batch: the segment scan at B=4 (8 CFG rows)
                        # compiles to 5.6 GiB on a described v5e
SEED = 0
PROMPTS = ("a watercolor fox in a snowy forest",
           "a lighthouse at dusk, oil painting",
           "a bowl of ramen, studio photo",
           "an isometric city block at night")
# bf16 weights and activations: each op rounds to ~2^-8 relative, and the
# kernel and the reference round at different points (online vs full
# softmax, bf16 vs f32 probabilities) over 24 layers: a sound step reads
# 1.071e-2 on a v5e.  This limit cannot see an attention fault of about
# 1% (the padded K rows unmasked as zeros read 1.970e-2); the attention
# check below can
STEP_REL_TOL = 2e-2
# the flash kernel alone at the joint sequence, f32 inputs, kernel and
# reference both at highest precision.  On a v5e a sound kernel reads
# 1.715e-6 (f32 summation order); the same kernel at default precision
# (bf16 matmul passes) reads 3.389e-3, and padded K rows left unmasked
# read 6.9e-3 (as zeros) and 1.15e-2 (as whatever the chip holds there)
ATTN_REL_TOL = 1e-4
SHARD_STEPS = 2
# k=4 against k=1 at the served guidance and at 0.5, so that every CFG row
# of every request enters the latent update.  g * (v_cond - v_uncond) in
# bf16 amplifies the rounding of two nearly equal branches, so each limit
# is about twice the k=1 floor measured at its guidance on a v5e: the same
# requests one at a time against batched, which differ only in XLA's
# program (4.5: 4.737e-2, 0.5: 6.456e-3).  The run prints the floor again
SHARD_REL_TOL = {4.5: 1e-1, 0.5: 1.3e-2}
# environment switches that would put a reference path in place of a kernel
_KERNEL_SWITCHES = ("REPRO_FLASH_ATTENTION", "REPRO_GROUPED_LORA_KERNEL",
                    "REPRO_QUANT_KERNEL")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got: Any, want: Any) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class CompileClock:
    """Seconds the XLA backend spends compiling, summed over programs."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# ------------------------------------------------------------ one chip
def serve_round(system: Any, n: int, seed0: int) -> tuple:
    """Submit ``n`` requests at once and run them to the end; returns
    (requests, images, wall seconds up to the last image being ready)."""
    import jax

    t0 = time.perf_counter()
    reqs = [system.submit("sd3:basic",
                          inputs={"seed": seed0 + i,
                                  "prompt": PROMPTS[i % len(PROMPTS)]})
            for i in range(n)]
    system.run()
    engine = system.coordinator.engine
    images = [engine.value_of(r.ref_key(r.graph.outputs["image"]))
              if r.status == "done" else None for r in reqs]
    jax.block_until_ready([im for im in images if im is not None])
    return reqs, images, time.perf_counter() - t0


def check_round(system: Any, reqs: List[Any], images: List[Any],
                image_px: int) -> None:
    import numpy as np

    co = system.coordinator
    check(not co.rejected and not co.shed,
          f"{len(co.rejected)} rejected, {len(co.shed)} shed")
    for r, im in zip(reqs, images):
        check(r.status == "done", f"request {r.rid}: {r.status}")
        check(tuple(im.shape) == (1, image_px, image_px, 3),
              f"request {r.rid}: image shape {tuple(im.shape)}")
        check(bool(np.isfinite(np.asarray(im)).all()),
              f"request {r.rid}: non-finite image")


def segment_components(system: Any) -> Dict[str, Any]:
    """The loaded components of the served sd3 denoise segment."""
    graph = system.registry.instantiate("sd3:basic")
    seg = next(n.op for n in graph.nodes if getattr(n.op, "is_segment", False))
    comps, _ = system.coordinator.backend.ensure_loaded(seg)
    return comps


def backbone_step_check(comps: Dict[str, Any], cfg: Any) -> Dict[str, Any]:
    """One backbone step: the served jitted apply (flash kernel) against
    the same step with ``mha(..., use_kernel=False)`` at highest matmul
    precision; also whether the compiled served step holds the kernel."""
    import jax
    import jax.numpy as jnp

    from repro.diffusion.mmdit import mmdit_apply
    from repro.nn.layers import set_flash_attention

    bb = comps["backbone"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(SEED + 1))
    lat = jax.random.normal(
        k1, (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels))
    emb = jax.random.normal(k2, (1, cfg.text_tokens, cfg.text_dim))
    t = jnp.full((1,), 0.5)
    res = jnp.zeros((cfg.n_layers, 1, cfg.image_tokens, cfg.d_model),
                    cfg.dtype)
    hlo = bb["apply"].lower(bb["params"], lat, t, emb, res).compile().as_text()
    served = bb["apply"](bb["params"], lat, t, emb, res)
    prev = set_flash_attention(False)
    try:
        with jax.default_matmul_precision("highest"):
            ref_step = jax.jit(
                lambda p, l, tt, e, r: mmdit_apply(p, cfg, l, tt, e, r))
            ref = ref_step(bb["params"], lat, t, emb, res)
    finally:
        set_flash_attention(prev)
    served, ref = jax.device_get((served, ref))
    return {"kernel_in_step": "tpu_custom_call" in hlo,
            "rel_err": rel_err(served, ref),
            "finite": bool(jnp.isfinite(served).all())}


def attention_check(cfg: Any, dtype: Any, head_dim: int) -> float:
    """The flash kernel against ``mha(..., use_kernel=False)`` at the
    backbone's joint sequence and heads, heads of ``head_dim``, both
    under highest precision, which Mosaic honours: what is left is f32
    summation noise, far below a padding or a precision fault."""
    import jax

    from repro.kernels.flash_attention.ops import mha

    shape = (1, cfg.tokens, cfg.n_heads, head_dim)
    q, k, v = (jax.random.normal(key, shape).astype(dtype)
               for key in jax.random.split(jax.random.PRNGKey(SEED + 2), 3))
    with jax.default_matmul_precision("highest"):
        got = mha(q, k, v)
        ref = mha(q, k, v, use_kernel=False)
    return rel_err(*jax.device_get((got, ref)))


def one_chip(fam: Any) -> None:
    import jax

    from repro.core import LocalBackend, ServingSystem
    from repro.diffusion import ModelSet, make_basic_workflow

    cfg = fam.dit
    image_px = cfg.latent_size * 8
    log(f"model: {fam.name} backbone d_model={cfg.d_model} "
        f"layers={cfg.n_layers} heads={cfg.n_heads} d_ff={cfg.d_ff} "
        f"latent={cfg.latent_size}x{cfg.latent_size}x{cfg.latent_channels} "
        f"text_tokens={cfg.text_tokens} dtype={jax.numpy.dtype(cfg.dtype)} "
        f"steps={fam.denoise_steps} cfg={fam.uses_cfg}")
    log("stand-ins: text encoder (2-layer transformer at text_dim) and "
        "VAE (3-stage conv) are the repository's own, not the published "
        "models; all weights random from a seed")
    clock = CompileClock()
    ms = ModelSet(fam)
    system = ServingSystem(n_executors=1, backend=LocalBackend())
    system.register(make_basic_workflow(fam.name, ms))
    log(f"scheduler hardware: {system.profiles.hw.name}")

    n_requests = N_REQUESTS
    reqs, images, cold_wall = serve_round(system, n_requests, SEED)
    check_round(system, reqs, images, image_px)
    cold_compile = clock.seconds
    log(f"cold round: {n_requests} requests done in {cold_wall:.3f} s wall "
        f"(compile {cold_compile:.3f} s of it)")

    reqs, images, wall = serve_round(system, n_requests, SEED + n_requests)
    check_round(system, reqs, images, image_px)
    log(f"images: {n_requests} x {tuple(images[0].shape)}, all finite, "
        f"all done, none rejected or shed")
    log(f"compile_seconds: {cold_compile:.3f} cold round, "
        f"{clock.seconds - cold_compile:.3f} warm round")
    # the requests are submitted together and run as one batch; the
    # coordinator's clock is modelled, so no request has a wall latency of
    # its own
    log(f"warm round wall time: {wall:.3f} s for {n_requests} requests "
        f"(submit to last image ready)")
    log(f"images_per_s (warm, batch {n_requests}): {n_requests / wall:.4f}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")

    step = backbone_step_check(segment_components(system), cfg)
    log(f"tpu_custom_call in backbone step: {step['kernel_in_step']}")
    log(f"backbone step, kernel vs reference attention at highest "
        f"precision: rel L2 err {step['rel_err']:.3e} "
        f"(tolerance {STEP_REL_TOL:g}: bf16 weights and activations)")
    check(step["finite"], "backbone step output not finite")
    check(step["kernel_in_step"], "no tpu_custom_call in the backbone step")
    check(step["rel_err"] <= STEP_REL_TOL,
          f"backbone step off the reference by {step['rel_err']:.3e}")
    # the backbone's head_dim (64) takes the row sum in the P.V pass's
    # spare lanes, 128 (FLUX's) on the VPU: check both of the kernel's paths
    for head_dim in (cfg.head_dim, 128):
        attn = attention_check(cfg, jax.numpy.float32, head_dim)
        log(f"flash kernel alone, {cfg.tokens} tokens x {cfg.n_heads} "
            f"heads x {head_dim}, f32 inputs, vs reference attention at "
            f"highest precision: rel L2 err {attn:.3e} (tolerance "
            f"{ATTN_REL_TOL:g}: above f32 summation noise, below bf16 "
            f"matmul passes and unmasked padded K rows)")
        check(attn <= ATTN_REL_TOL, f"flash kernel at head_dim {head_dim} "
              f"off the reference by {attn:.3e}")


# ---------------------------------------------------------- four chips
def segment_kwargs(cfg: Any, guidance: Sequence[float],
                   seed: int) -> List[Dict[str, Any]]:
    """One segment request per guidance value."""
    import jax

    from repro.diffusion.sampler import flow_schedule

    sched = [float(x) for x in flow_schedule(SHARD_STEPS)]
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(guidance))
    return [{
        "latents": jax.random.normal(
            keys[2 * i], (1, cfg.latent_size, cfg.latent_size,
                          cfg.latent_channels)),
        "prompt_embeds": jax.random.normal(
            keys[2 * i + 1], (1, cfg.text_tokens, cfg.text_dim)),
        "t_mid": tuple(sched[:-1]), "t_cur": tuple(sched[:-1]),
        "t_next": tuple(sched[1:]), "guidance": g,
    } for i, g in enumerate(guidance)]


def update_err(got: List[Dict[str, Any]], want: List[Dict[str, Any]],
               kws: List[Dict[str, Any]]) -> float:
    """Worst rel L2 error of the latent updates: the unchanged input
    latents would dilute the error."""
    return max(rel_err(o["latents"] - kw["latents"],
                       r["latents"] - kw["latents"])
               for o, r, kw in zip(got, want, kws))


class SegmentRunner:
    """Runs one ``DenoiseSegment`` batch through a ``ShardedBackend``, on
    one device (``mesh=None``) or on a submesh, to the end on device."""

    def __init__(self, fam: Any) -> None:
        from repro.core import MeshManager, ShardedBackend
        from repro.diffusion.ops import DiffusionBackbone

        self.mm = MeshManager()
        self.backend = ShardedBackend(self.mm)
        self.seg = DiffusionBackbone(fam).build_segment([], SHARD_STEPS)

    def __call__(self, kws: List[Dict[str, Any]], mesh: Any = None) -> tuple:
        import jax

        t0 = time.perf_counter()
        outs, _, _ = self.backend.execute_batch(
            self.seg, [dict(k) for k in kws], mesh=mesh)
        jax.block_until_ready(outs)
        return outs, time.perf_counter() - t0

    def k1_floor(self, kws: List[Dict[str, Any]]) -> tuple:
        """The requests batched and one at a time on one device; returns
        (batched outputs, solo outputs, floor, warm batched seconds)."""
        self(kws)
        batched, warm = self(kws)
        solo = [self([kw])[0][0] for kw in kws]
        return batched, solo, update_err(solo, batched, kws), warm


def four_chips(fam: Any) -> None:
    import jax

    cfg = fam.dit
    check(jax.device_count() == 4, f"{jax.device_count()} devices, need 4")
    run = SegmentRunner(fam)
    check(run.backend.enabled, "sharded execution disabled")
    mesh = run.mm.submesh([0, 1, 2, 3])
    log(f"model: {fam.name} DenoiseSegment d_model={cfg.d_model} "
        f"layers={cfg.n_layers} latent={cfg.latent_size}, "
        f"{SHARD_STEPS} steps, CFG")

    for g, tol in SHARD_REL_TOL.items():
        kws = segment_kwargs(cfg, (g, g), SEED)
        batched, solo, floor, ref_warm = run.k1_floor(kws)
        log(f"guidance {g}: k=1 floor (one request at a time vs batched, "
            f"one chip): rel L2 err of the latent update {floor:.3e}")
        # B=2 folds to 4 CFG rows, one per device; B=1 to 2 rows, which
        # the scan shards along the sequence
        for mode, sub, ref in (("cfg_rows", kws, batched),
                               ("sequence", kws[:1], solo[:1])):
            n_sharded = len(run.backend.shard_log)
            run(sub, mesh)
            out, warm = run(sub, mesh)
            check(len(run.backend.shard_log) == n_sharded + 2,
                  f"{mode}: k=4 fell back to the single-device forward")
            _, _, k, dev_ids = run.backend.shard_log[-1]
            check(k == 4 and len(set(dev_ids)) == 4,
                  f"{mode}: shard_log k={k} devices={dev_ids}")
            err = update_err(out, ref, sub)
            log(f"guidance {g}: {mode} B={len(sub)} ({2 * len(sub)} rows) "
                f"k=4 on devices {list(dev_ids)}: rel L2 err of the latent "
                f"update vs k=1 {err:.3e} (tolerance {tol:g}); warm wall "
                f"k=4 {warm:.3f} s, k=1 batched B=2 {ref_warm:.3f} s")
            check(err <= tol, f"guidance {g}: {mode}: k=4 off k=1 by "
                  f"{err:.3e}")

    kws = segment_kwargs(cfg, (4.5,), SEED + 1)
    for eid in (1, 2, 3):
        outs, _ = run(kws, run.mm.submesh([eid]))
        ids = sorted(d.id for d in outs[0]["latents"].devices())
        log(f"executor {eid} owns device {run.mm.device_of(eid).id}; "
            f"its k=1 forward landed on device(s) {ids}")


# ---------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    forced = [v for v in _KERNEL_SWITCHES
              if os.environ.get(v, "").lower() in ("0", "false", "off")]
    if forced:
        print(f"refusing to run: {', '.join(forced)} forces a reference "
              f"path in place of a kernel", file=sys.stderr)
        return 2

    import jax

    from repro.core import use_compile_cache
    from repro.diffusion.config import SD3

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x {jax.device_count()}")
    log(f"compile cache: {use_compile_cache()}")
    fam = SD3.at_published_width()
    try:
        if args.chips == 4:
            four_chips(fam)
        else:
            one_chip(fam)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
