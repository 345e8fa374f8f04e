"""Batched execution engine parity: stacked cross-request forwards match
per-request sequential outputs, one-pass CFG matches two-pass, and the
Pallas flash-attention route matches the reference attention on MMDiT
joint text+image shapes."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalBackend, Scheduler, ServingSystem
from repro.diffusion import (
    FAMILIES,
    ModelSet,
    make_basic_workflow,
    make_controlnet_workflow,
    make_lora_workflow,
)
from repro.diffusion.mmdit import init_mmdit, mmdit_apply
from repro.diffusion.sampler import cfg_velocity, fused_cfg_velocity
from repro.diffusion.serving import DenoiseStep, DiffusionBackbone, LoRAAdapter
from repro.kernels.flash_attention.ops import mha
from repro.nn.layers import gqa_attention, set_flash_attention

KEY = jax.random.PRNGKey(0)
FAM = FAMILIES["sd3"]
CFG = FAM.dit


def _batch_kwargs_backbone(n, with_residuals=False):
    ks = jax.random.split(KEY, 2 * n + 1)
    out = []
    for i in range(n):
        kw = {
            "latents": jax.random.normal(
                ks[2 * i], (1, CFG.latent_size, CFG.latent_size,
                            CFG.latent_channels)),
            "prompt_embeds": jax.random.normal(
                ks[2 * i + 1], (1, CFG.text_tokens, CFG.text_dim)),
            "t": 0.25 + 0.1 * i,            # heterogeneous timesteps
            "guidance": 3.0 + i,            # heterogeneous guidance
        }
        if with_residuals:
            kw["controlnet_residuals"] = 0.01 * jax.random.normal(
                ks[-1], (CFG.n_layers, 1, CFG.image_tokens, CFG.d_model))
        out.append(kw)
    return out


def _assert_batch_matches_sequential(model, batch_kwargs, atol=1e-4):
    comps = model.load()
    batched = model.execute_batch(comps, batch_kwargs)
    sequential = [model.execute(comps, **kw) for kw in batch_kwargs]
    assert len(batched) == len(sequential)
    for got, want in zip(batched, sequential):
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_allclose(
                np.asarray(got[name], np.float32),
                np.asarray(want[name], np.float32), atol=atol, rtol=atol,
                err_msg=f"{model.model_id}.{name}")


def test_text_encoder_batch_parity():
    ms = ModelSet(FAM)
    _assert_batch_matches_sequential(
        ms.text_enc,
        [{"prompt": p} for p in ("a fox", "two foxes in the snow", "x")])


def test_backbone_batch_parity():
    ms = ModelSet(FAM)
    _assert_batch_matches_sequential(ms.backbone, _batch_kwargs_backbone(3))


def test_backbone_batch_parity_with_residuals():
    ms = ModelSet(FAM)
    _assert_batch_matches_sequential(
        ms.backbone, _batch_kwargs_backbone(2, with_residuals=True))


def test_controlnet_batch_parity():
    ms = ModelSet(FAM)
    ks = jax.random.split(KEY, 6)
    shape = (1, CFG.latent_size, CFG.latent_size, CFG.latent_channels)
    kwargs = [
        {
            "latents": jax.random.normal(ks[2 * i], shape),
            "cond_latents": jax.random.normal(ks[2 * i + 1], shape),
            "prompt_embeds": jax.random.normal(
                ks[4 + i], (1, CFG.text_tokens, CFG.text_dim)),
            "t": 0.5,
        }
        for i in range(2)
    ]
    _assert_batch_matches_sequential(ms.cn1, kwargs)


def test_vae_batch_parity():
    ms = ModelSet(FAM)
    shape = (1, CFG.latent_size, CFG.latent_size, CFG.latent_channels)
    lat_kwargs = [{"latents": jax.random.normal(k, shape)}
                  for k in jax.random.split(KEY, 3)]
    _assert_batch_matches_sequential(ms.vae_dec, lat_kwargs)
    img_shape = (1, CFG.latent_size * 8, CFG.latent_size * 8, 3)
    img_kwargs = [{"image": jax.random.normal(k, img_shape)}
                  for k in jax.random.split(KEY, 2)]
    img_kwargs.append({"image": None})       # toy PIL stand-in
    _assert_batch_matches_sequential(ms.vae_enc, img_kwargs)


def test_trivial_nodes_batch_parity():
    ms = ModelSet(FAM)
    _assert_batch_matches_sequential(
        ms.latents, [{"seed": s} for s in (0, 7, 123)], atol=0)
    shape = (1, CFG.latent_size, CFG.latent_size, CFG.latent_channels)
    ks = jax.random.split(KEY, 4)
    step = DenoiseStep(FAM)
    _assert_batch_matches_sequential(step, [
        {"latents": jax.random.normal(ks[2 * i], shape),
         "velocity": jax.random.normal(ks[2 * i + 1], shape),
         "t_cur": 0.5, "t_next": 0.25}
        for i in range(2)
    ])


def test_fallback_forward_accounting():
    """An unstackable batch falls back to per-request execution AND the
    backend's forward_log records the N real forwards, not one of size N."""
    backend = LocalBackend()
    ms = ModelSet(FAM)
    ks = jax.random.split(KEY, 2)
    kws = [{"latents": jax.random.normal(ks[0], (1, 16, 16, 4))},
           {"latents": jax.random.normal(ks[1], (1, 8, 8, 4))}]
    outs, _, _ = backend.execute_batch(ms.vae_dec, kws)
    assert [n for _, n in backend.forward_log] == [1, 1]
    assert outs[0]["image"].shape == (1, 128, 128, 3)
    assert outs[1]["image"].shape == (1, 64, 64, 3)


def test_backend_execute_batch_lifts_uniform_patches():
    """Direct callers passing a uniform per-request ``_patches`` kwarg get
    the same backend-cached fold as the serving runtime's ``patches=``."""
    lora = LoRAAdapter(FAM, "lifted")
    kws = _batch_kwargs_backbone(2)
    backend = LocalBackend()
    patched, _, _ = backend.execute_batch(
        DiffusionBackbone(FAM),
        [dict(kw, _patches=[lora]) for kw in kws])
    assert len(backend._folded) == 1
    base, _, _ = LocalBackend().execute_batch(DiffusionBackbone(FAM), kws)
    delta = np.abs(np.asarray(patched[0]["velocity"])
                   - np.asarray(base[0]["velocity"])).max()
    assert delta > 1e-6, "lifted patches must alter the output"


def test_fused_cfg_matches_two_pass():
    params = init_mmdit(jax.random.PRNGKey(1), CFG)
    lat = jax.random.normal(
        KEY, (2, CFG.latent_size, CFG.latent_size, CFG.latent_channels))
    emb = jax.random.normal(KEY, (2, CFG.text_tokens, CFG.text_dim))
    t = jnp.full((2,), 0.4)
    two_pass = cfg_velocity(params, CFG, lat, t, emb, jnp.zeros_like(emb),
                            guidance=4.5)
    fused = fused_cfg_velocity(
        lambda p, l, tt, e, r: mmdit_apply(p, CFG, l, tt, e, r),
        params, lat, t, emb, guidance=4.5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_pass),
                               atol=1e-4, rtol=1e-4)


def test_mha_matches_gqa_on_joint_shapes():
    """MMDiT joint text+image non-causal self-attention (interpret mode)."""
    prev = set_flash_attention(False)        # reference arm
    try:
        for seq in (CFG.text_tokens + CFG.image_tokens, 128):
            ks = jax.random.split(KEY, 3)
            q = jax.random.normal(ks[0], (2, seq, CFG.n_heads, CFG.head_dim))
            k = jax.random.normal(ks[1], (2, seq, CFG.n_heads, CFG.head_dim))
            v = jax.random.normal(ks[2], (2, seq, CFG.n_heads, CFG.head_dim))
            out = mha(q, k, v, causal=False)
            ref = gqa_attention(q, k, v, causal=False)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)
    finally:
        set_flash_attention(prev)


def test_flash_route_toggle_is_transparent():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 72, 4, 16))
    k = jax.random.normal(ks[1], (1, 72, 4, 16))
    v = jax.random.normal(ks[2], (1, 72, 4, 16))
    prev = set_flash_attention(True)
    try:
        routed = gqa_attention(q, k, v, causal=False)
        set_flash_attention(False)
        reference = gqa_attention(q, k, v, causal=False)
    finally:
        set_flash_attention(prev)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(reference),
                               atol=2e-5, rtol=2e-5)


def test_flash_route_is_differentiable():
    """The kernel's custom_vjp (reference backward) keeps training paths
    that share gqa_attention's non-causal route differentiable."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 4, 16))
    v = jax.random.normal(ks[2], (1, 64, 4, 16))

    def loss(q, k, v):
        return (gqa_attention(q, k, v, causal=False) ** 2).sum()

    prev = set_flash_attention(True)
    try:
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        set_flash_attention(False)
        ref_grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        set_flash_attention(prev)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# Executable plane end to end
# --------------------------------------------------------------------------

def _run_plane(wf, inputs_list, max_batch_cap=None, steps=2, n_exec=1):
    backend = LocalBackend()
    sys_ = ServingSystem(n_executors=n_exec, backend=backend)
    if max_batch_cap is not None:
        sys_.coordinator.scheduler = Scheduler(
            sys_.profiles, max_batch_cap=max_batch_cap,
            use_declared_max_batch=True)
    sys_.register(wf)
    reqs = [sys_.submit(wf.name, inputs=inp, arrival=0.0, steps=steps)
            for inp in inputs_list]
    sys_.run()
    imgs = []
    for r in reqs:
        assert r.status == "done"
        img = sys_.coordinator.engine.value_of(
            r.ref_key(r.graph.outputs["image"]))
        imgs.append(np.asarray(img))
    return imgs, sys_, backend


def test_end_to_end_batched_matches_sequential():
    inputs = [{"seed": i, "prompt": f"probe {i}"} for i in range(3)]
    wf = make_basic_workflow("sd3")
    batched, _, _ = _run_plane(wf, inputs)
    sequential, _, _ = _run_plane(make_basic_workflow("sd3"), inputs,
                                  max_batch_cap=1)
    for b, s in zip(batched, sequential):
        np.testing.assert_allclose(b, s, atol=1e-4, rtol=1e-4)


def test_one_forward_per_scheduled_batch():
    inputs = [{"seed": i, "prompt": "shared prompt"} for i in range(4)]
    _, sys_, backend = _run_plane(make_basic_workflow("sd3"), inputs, steps=2)
    seg_fwd = [n for mid, n in backend.forward_log
               if mid == "segment:backbone:sd3"]
    seg_dispatches = [b for b in sys_.coordinator.dispatch_log
                      if b.model_id == "segment:backbone:sd3"]
    # one backend forward per (model, ScheduledBatch); the fused segment
    # stacks all 4 requests AND both denoise steps into a single scan
    assert len(seg_fwd) == len(seg_dispatches) == 1
    assert seg_fwd == [4]
    assert seg_dispatches[0].segment_steps == 2
    text_fwd = [n for mid, n in backend.forward_log if mid == "text_encoder:sd3"]
    assert sum(text_fwd) == 4


def test_lora_fold_and_adapter_load_cached(monkeypatch):
    calls = {"n": 0}
    orig = LoRAAdapter.load

    def counting_load(self, device=None):
        calls["n"] += 1
        return orig(self, device)

    monkeypatch.setattr(LoRAAdapter, "load", counting_load)
    wf = make_lora_workflow("sd3", "style")
    imgs, _, backend = _run_plane(wf, [{"seed": 3, "prompt": "styled"}],
                                  steps=3)
    assert np.isfinite(imgs[0]).all()
    # adapter loaded once (memoized), folded once per (model_id, patch_ids)
    assert calls["n"] == 1
    assert len(backend._folded) == 1


def test_controlnet_workflow_batched_end_to_end():
    inputs = [{"seed": i, "prompt": "cn", "ref_image": None} for i in range(2)]
    batched, _, _ = _run_plane(make_controlnet_workflow("sd3", 1), inputs)
    sequential, _, _ = _run_plane(make_controlnet_workflow("sd3", 1), inputs,
                                  max_batch_cap=1)
    for b, s in zip(batched, sequential):
        np.testing.assert_allclose(b, s, atol=1e-4, rtol=1e-4)


def test_prng_stable_across_hash_seeds():
    """Two processes with different PYTHONHASHSEED agree on tokenization
    and model-seed derivation (zlib.crc32, not the salted builtin hash)."""
    code = (
        "from repro.diffusion.encoders import tokenize, stable_hash\n"
        "import numpy as np\n"
        "print(np.asarray(tokenize('a fox jumps', 512, 8)).tolist(),"
        " stable_hash('backbone:sd3'))\n"
    )
    outs = []
    for hs in ("0", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=hs,
                   PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, cwd=os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip())
    assert outs[0] == outs[1]
