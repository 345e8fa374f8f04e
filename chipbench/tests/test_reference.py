"""The plain reference against the program's own forwards, at toy width
on the CPU: the weights it rebuilds from the program's seeds are the
program's, and both compute the same functions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mmdit as ref
from chipbench.reference import standins

TOY = dict(family="sd3", d_model=64, n_layers=2, n_heads=4, d_ff=256,
           text_dim=64, latent_size=16, latent_channels=4, patch=2,
           text_tokens=8, te_vocab=512, te_layers=2, te_heads=4,
           te_dtype="float32", vae_base=32, vae_dtype="float32")


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def program_dit(dtype):
    from repro.diffusion.config import DiTConfig

    keys = ("d_model", "n_layers", "n_heads", "d_ff", "text_dim",
            "latent_size", "latent_channels", "patch", "text_tokens")
    return DiTConfig(**{k: TOY[k] for k in keys}, dtype=dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_backbone_velocity(dtype, tol):
    from repro.diffusion.encoders import stable_hash
    from repro.diffusion.mmdit import init_mmdit, mmdit_apply

    g = ref.Geometry(dtype=jnp.dtype(dtype).name, **TOY)
    cfg = program_dit(dtype)
    params = init_mmdit(
        jax.random.PRNGKey(stable_hash("backbone:sd3") % 2**31), cfg)
    outer = ref.outer_weights(ref.model_key("backbone:sd3"), g)
    layer = ref.layer_weights(outer["layers"][1], g)
    # the same draws, to the last bit of the served dtype (XLA may fuse
    # the f32 scale differently under jit and eagerly: 1 ulp)
    for stream in ("img", "txt"):
        for name in ("ada", "wq", "w2"):
            np.testing.assert_allclose(
                params["layers"][stream][name][1].astype(jnp.float32),
                layer[stream][name], rtol=2.5e-7, atol=0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    lat = jax.random.normal(k1, (2, 16, 16, 4))
    emb = jax.random.normal(k2, (2, 8, 64))
    t = jnp.array([0.7, 0.3])
    with jax.default_matmul_precision("highest"):
        got = mmdit_apply(params, cfg, lat, t, emb)
    assert rel(got, ref.velocity(g, lat, t, emb)) < tol


def test_guided_step_matches_the_program_segment():
    from repro.diffusion import SD3
    from repro.diffusion.ops import DenoiseSegment, DiffusionBackbone
    import dataclasses

    g = ref.Geometry(dtype="float32", **TOY)
    fam = dataclasses.replace(SD3, dit=program_dit(jnp.float32))
    seg = DenoiseSegment(DiffusionBackbone(fam), [], 4)
    comps = seg.load()
    lat = ref.initial_latents(g, 11)
    emb = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 64))
    sched = [float(x) for x in ref.flow_schedule(4)]
    kw = {"latents": lat, "prompt_embeds": emb, "t_mid": tuple(sched[:-1]),
          "t_cur": tuple(sched[:-1]), "t_next": tuple(sched[1:]),
          "guidance": 7.0, "_seg_start": 1, "_seg_steps": 2}
    with jax.default_matmul_precision("highest"):
        got = seg.execute(comps, **kw)["latents"]
    want = ref.sample(g, lat, emb, 4, 7.0, start=1, stop=3)
    assert rel(got - lat, want - lat) < 1e-5


def test_text_encoder_and_vae():
    from repro.diffusion.encoders import (init_text_encoder, init_vae,
                                          stable_hash, text_encoder_apply,
                                          tokenize_batch, vae_decode)

    g = ref.Geometry(dtype="float32", **TOY)
    prompts = ["a red fox in the snow", "the lighthouse at dusk, oil"]
    te = init_text_encoder(
        jax.random.PRNGKey(stable_hash("text_encoder:sd3") % 2**31), 512, 64,
        n_layers=2, n_heads=4, max_len=8)
    with jax.default_matmul_precision("highest"):
        got = text_encoder_apply(te, tokenize_batch(prompts, 512, 8), n_heads=4)
    assert rel(got, standins.encode(g, prompts)) < 1e-5
    vae = init_vae(jax.random.PRNGKey(stable_hash("vae:sd3") % 2**31),
                   latent_channels=4)
    lat = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 16, 4))
    with jax.default_matmul_precision("highest"):
        img = vae_decode(vae, lat)
    assert img.shape == (1, 128, 128, 3)
    assert rel(img, standins.decode(g, lat)) < 1e-5
