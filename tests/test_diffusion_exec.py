"""Executable diffusion plane: real tensors end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GraphCompiler, LocalBackend, ServingSystem
from repro.core.passes import ApproximateCachingPass, InlineTrivialPass, JitCompilePass
from repro.diffusion import (
    ApproxCache,
    FAMILIES,
    ModelSet,
    make_basic_workflow,
    make_controlnet_workflow,
    make_lora_workflow,
)
from repro.diffusion.lora import fold_lora, init_lora, randomize_lora, unfold_lora
from repro.diffusion.mmdit import init_mmdit, mmdit_apply
from repro.diffusion.sampler import cfg_combine, denoise_step, flow_schedule


def _run_wf(wf, inputs, steps=3, n_exec=2):
    sys_ = ServingSystem(n_executors=n_exec, backend=LocalBackend())
    sys_.register(wf)
    r = sys_.submit(wf.name, inputs=inputs, steps=steps)
    sys_.run()
    assert r.status == "done"
    img = sys_.coordinator.engine.value_of(r.ref_key(r.graph.outputs["image"]))
    assert img is not None
    arr = np.asarray(img)
    assert arr.shape == (1, 128, 128, 3)
    assert np.isfinite(arr).all()
    return arr


def test_basic_workflow_produces_image():
    _run_wf(make_basic_workflow("sd3"), {"seed": 0, "prompt": "a fox"})


def test_controlnet_workflow_produces_image():
    _run_wf(make_controlnet_workflow("sd3", 1),
            {"seed": 0, "prompt": "a fox", "ref_image": None})


def test_lora_workflow_changes_output():
    base = _run_wf(make_basic_workflow("flux-schnell"),
                   {"seed": 5, "prompt": "style probe"})
    styled = _run_wf(make_lora_workflow("flux-schnell", "style"),
                     {"seed": 5, "prompt": "style probe"})
    assert np.abs(base - styled).max() > 1e-6, "LoRA patch must alter output"


def test_seed_determinism():
    a = _run_wf(make_basic_workflow("sd3"), {"seed": 7, "prompt": "same"})
    b = _run_wf(make_basic_workflow("sd3"), {"seed": 7, "prompt": "same"})
    np.testing.assert_allclose(a, b)


def test_lora_fold_unfold_roundtrip():
    cfg = FAMILIES["sd3"].dit
    params = init_mmdit(jax.random.PRNGKey(0), cfg)
    lora = randomize_lora(jax.random.PRNGKey(1),
                          init_lora(jax.random.PRNGKey(2), cfg))
    folded = fold_lora(params, lora)
    diff = jnp.abs(folded["layers"]["img"]["wq"]
                   - params["layers"]["img"]["wq"]).max()
    assert float(diff) > 0
    restored = unfold_lora(folded, lora)
    np.testing.assert_allclose(
        np.asarray(restored["layers"]["img"]["wq"]),
        np.asarray(params["layers"]["img"]["wq"]), atol=1e-5)


def test_controlnet_residuals_modulate_backbone():
    cfg = FAMILIES["sd3"].dit
    params = init_mmdit(jax.random.PRNGKey(0), cfg)
    lat = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 4))
    emb = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 64))
    t = jnp.full((1,), 0.5)
    v0 = mmdit_apply(params, cfg, lat, t, emb)
    res = jnp.ones((cfg.n_layers, 1, cfg.image_tokens, cfg.d_model)) * 0.1
    v1 = mmdit_apply(params, cfg, lat, t, emb, control_residuals=res)
    assert float(jnp.abs(v1 - v0).max()) > 1e-6


def test_flow_schedule_monotone():
    s = flow_schedule(10)
    assert float(s[0]) == 1.0 and float(s[-1]) == 0.0
    assert np.all(np.diff(np.asarray(s)) < 0)


def test_cfg_combine_identities():
    vu = jnp.ones((2, 3))
    vc = 2 * jnp.ones((2, 3))
    np.testing.assert_allclose(np.asarray(cfg_combine(vu, vc, 1.0)),
                               np.asarray(vc))
    np.testing.assert_allclose(np.asarray(cfg_combine(vu, vc, 0.0)),
                               np.asarray(vu))


def test_approx_cache_executable_plane():
    """Caching pass + executable run: cached latent skips early steps."""
    cache = ApproxCache(similarity_threshold=0.0)
    lat = jax.random.normal(jax.random.PRNGKey(9), (1, 16, 16, 4))
    cache.insert("a warm prompt", 2, lat)
    passes = [ApproximateCachingPass(cache, "backbone:sd3", skip_fraction=0.5),
              InlineTrivialPass(), JitCompilePass()]
    sys_ = ServingSystem(n_executors=2, backend=LocalBackend(),
                         extra_passes=passes)
    wf = make_basic_workflow("sd3")
    sys_.register(wf)
    r = sys_.submit(wf.name, inputs={"seed": 0, "prompt": "a warm prompt"},
                    steps=4)
    sys_.run()
    assert r.status == "done"
    assert cache.hits >= 1
