"""The FLUX backbone on the served path, at toy width on the CPU: 3-axis
RoPE, guidance as a backbone input, one backbone row per request-step,
and the paths FLUX refuses (ControlNet, sequence sharding) or serves
row-sharded.  Its agreement with the plain reference is
``chipbench/tests/test_flux.py``."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.diffusion import FAMILIES
from repro.diffusion.flux import apply_rope, init_flux, rope_angles
from repro.diffusion.ops import ControlNet, DenoiseSegment, DiffusionBackbone

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rope_at_position_zero_is_the_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    np.testing.assert_array_equal(apply_rope(x, jnp.zeros((5, 8))), x)
    # text tokens all sit at (0, 0, 0)
    cfg = FAMILIES["flux-dev"].dit
    assert not np.asarray(rope_angles(cfg)[:cfg.text_tokens]).any()


def test_rope_scores_depend_only_on_the_offset():
    cfg = FAMILIES["flux-dev"].dit
    side = cfg.latent_size // cfg.patch
    angles = rope_angles(cfg)
    kq, kk = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(kq, (cfg.head_dim,))
    k = jax.random.normal(kk, (cfg.head_dim,))
    tok = lambda r, c: cfg.text_tokens + r * side + c
    spread = lambda v: jnp.broadcast_to(v, (1, cfg.tokens, 1, cfg.head_dim))
    qr, kr = apply_rope(spread(q), angles)[0, :, 0], \
        apply_rope(spread(k), angles)[0, :, 0]
    score = lambda a, b: float(qr[tok(*a)] @ kr[tok(*b)])
    for (a, b), shift in [(((1, 2), (4, 0)), (2, 3)),
                          (((0, 4), (5, 7)), (1, -4)),
                          (((6, 6), (6, 6)), (-6, -5))]:
        moved = [(p[0] + shift[0], p[1] + shift[1]) for p in (a, b)]
        assert score(a, b) == pytest.approx(score(*moved), rel=1e-5)
    # and they do depend on it
    assert score((0, 0), (0, 3)) != pytest.approx(score((0, 0), (3, 0)))


@pytest.mark.parametrize("family,moves", [("flux-dev", True),
                                          ("flux-schnell", False)])
def test_guidance_is_an_input_of_the_backbone(family, moves):
    fam = FAMILIES[family]
    op = DiffusionBackbone(fam)
    comps = op.load()
    cfg = fam.dit
    lat = jax.random.normal(jax.random.PRNGKey(2),
                            (1, cfg.latent_size, cfg.latent_size,
                             cfg.latent_channels))
    emb = jax.random.normal(jax.random.PRNGKey(3),
                            (1, cfg.text_tokens, cfg.text_dim))
    v = [np.asarray(op.execute(comps, latents=lat, prompt_embeds=emb, t=0.6,
                               guidance=g)["velocity"]) for g in (1.0, 4.0)]
    assert (np.abs(v[0] - v[1]).max() > 1e-3) == moves


@pytest.mark.parametrize("family,rows_per_request", [("flux-dev", 1),
                                                     ("sd3", 2)])
def test_segment_dispatch_runs_one_backbone_row_per_request(
        monkeypatch, family, rows_per_request):
    import repro.diffusion.ops as ops

    name = "flux_apply" if family.startswith("flux") else "mmdit_apply"
    real, rows = getattr(ops, name), []

    def counted(params, cfg, lat, *args, **kwargs):
        rows.append(lat.shape[0])
        return real(params, cfg, lat, *args, **kwargs)

    monkeypatch.setattr(ops, name, counted)
    fam = FAMILIES[family]
    seg = DenoiseSegment(DiffusionBackbone(fam), [], 2)
    comps = seg.load()
    cfg = fam.dit
    kws = [{"latents": jnp.zeros((1, cfg.latent_size, cfg.latent_size,
                                  cfg.latent_channels)),
            "prompt_embeds": jnp.ones((1, cfg.text_tokens, cfg.text_dim)),
            "t_mid": (1.0, 0.5), "t_cur": (1.0, 0.5), "t_next": (0.5, 0.0),
            "guidance": 3.5} for _ in range(3)]
    out = seg.execute_batch(comps, kws)
    assert len(out) == 3
    assert rows == [3 * rows_per_request]     # traced once, inside the scan


def test_flux_refuses_controlnet_at_load_and_sequence_sharding():
    fam = FAMILIES["flux-dev"]
    with pytest.raises(NotImplementedError, match="no FLUX ControlNet"):
        ControlNet(fam).load()
    seg = DenoiseSegment(DiffusionBackbone(fam), [], 4)
    # one request on four devices: only sequence sharding would fit
    assert seg.clamp_parallelism(1, 4) == 1
    assert seg.clamp_parallelism(4, 4) == 4
    sd3 = DenoiseSegment(DiffusionBackbone(FAMILIES["sd3"]), [], 4)
    assert sd3.clamp_parallelism(1, 4) == 4


def test_row_sharded_flux_segment_matches_one_device():
    """Two virtual CPU devices in a subprocess: a 2-request flux-dev
    segment, and a backbone forward, sharded by rows equal the
    single-device ones, and one request (which only sequence sharding
    could split) is declined."""
    code = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.diffusion import FAMILIES
        from repro.diffusion.ops import DenoiseSegment, DiffusionBackbone
        fam = FAMILIES["flux-dev"]
        cfg = fam.dit
        seg = DenoiseSegment(DiffusionBackbone(fam), [], 2)
        comps = seg.load()
        shape = (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)
        kws = [{"latents": jax.random.normal(jax.random.PRNGKey(i), shape),
                "prompt_embeds": jnp.full((1, cfg.text_tokens, cfg.text_dim),
                                          0.1 * (i + 1)),
                "t_mid": (1.0, 0.5), "t_cur": (1.0, 0.5),
                "t_next": (0.5, 0.0), "guidance": 2.0 + i}
               for i in range(2)]
        mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
        got = seg.execute_batch_sharded(comps, kws, mesh)
        want = seg.execute_batch(comps, kws)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g["latents"]),
                                       np.asarray(w["latents"]),
                                       rtol=1e-5, atol=1e-5)
        assert seg.execute_batch_sharded(comps, kws[:1], mesh) is None
        bb = DiffusionBackbone(fam)
        bc = bb.load()
        bk = [{"latents": kw["latents"], "prompt_embeds": kw["prompt_embeds"],
               "t": 0.5, "guidance": kw["guidance"]} for kw in kws]
        for g, w in zip(bb.execute_batch_sharded(bc, bk, mesh),
                        bb.execute_batch(bc, bk)):
            np.testing.assert_allclose(np.asarray(g["velocity"]),
                                       np.asarray(w["velocity"]),
                                       rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")


def test_backbone_params_are_counted_from_the_geometry():
    from repro.diffusion.config import FLUX_DEV_DIT

    fam = FAMILIES["flux-dev"]
    assert fam.backbone_params == FLUX_DEV_DIT.n_params
    assert 11.8e9 < fam.backbone_params < 12.0e9
    shapes = jax.eval_shape(lambda k: init_flux(k, fam.dit),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == fam.dit.n_params
    # the CPU toy stands in for the whole model; a stage (published widths,
    # fewer blocks) is charged what it holds, and fits one 16 GiB chip
    assert fam.backbone_bytes() == 2.0 * FLUX_DEV_DIT.n_params
    stage = dataclasses.replace(fam, dit=dataclasses.replace(
        FLUX_DEV_DIT, n_double=6, n_single=12))
    assert stage.backbone_bytes() == 2.0 * stage.dit.n_params < 16 * 2**30
    assert FAMILIES["flux-dev"].at_published_width().backbone_bytes() == \
        fam.backbone_bytes()
