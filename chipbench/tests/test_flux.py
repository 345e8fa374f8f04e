"""The FLUX reference (``chipbench/reference/flux.py``) against the
program, at toy width on the CPU: the weights it rebuilds are the
program's, one backbone forward agrees, a served flux-dev cell passes its
check while the float8 reference in its place does not, and the cell's
configuration, counts and block metrics are found by name."""

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, xplane
from chipbench.reference import flux as ref

BENCH = Path(__file__).resolve().parents[1]
CELL = "flux-stage.backlog"
# toy readings on the CPU (f32 weights): text_embed_gap about 5e-7,
# latent_update_gap 1e-6 to 3e-6, image_gap 1e-6; the float8 reference
# reads 0.08-0.10 on the text embedding, 0.03 on the latent update and
# the image
TOY_LIMITS = {"text_embed_gap": 1e-4, "latent_update_gap": 1e-2,
              "image_gap": 1e-2}


def stage_config() -> dict:
    return json.loads((BENCH / "configs" / "flux.1-dev-stage.json").read_text())


def toy_config(dtype: str = "float32") -> dict:
    cfg = stage_config()
    cfg.update(num_layers=1, num_single_layers=2, attention_head_dim=16,
               num_attention_heads=4, joint_attention_dim=64,
               pooled_projection_dim=16, latent_size=16, in_channels=16,
               text_tokens=8, axes_dims_rope=[4, 6, 6], batch_cap=4,
               dtype=dtype)
    cfg["text_encoder"] = dict(cfg["text_encoder"], width=64)
    return cfg


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
def test_backbone_velocity(dtype, tol):
    from repro.diffusion.encoders import stable_hash
    from repro.diffusion.flux import flux_apply, init_flux

    cfg = toy_config(dtype)
    g = ref.geometry_from_config(cfg)
    dit = harness.family_for(cfg, check_published=False).dit
    params = init_flux(
        jax.random.PRNGKey(stable_hash("backbone:flux-dev") % 2**31), dit)
    outer = ref.outer_weights(ref.model_key("backbone:flux-dev"), g)
    single = ref.single_weights(outer["single"][1], g)
    # the same draws, to the last bit of the served dtype
    for name in ("ada", "w1", "b2"):
        np.testing.assert_allclose(
            params["single"][name][1].astype(jnp.float32), single[name],
            rtol=2.5e-7, atol=0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    lat = jax.random.normal(k1, (2, 16, 16, 4))
    emb = jax.random.normal(k2, (2, 8, 64))
    t, gd = jnp.array([0.7, 0.3]), jnp.array([3.5, 1.0])
    with jax.default_matmul_precision("highest"):
        got = flux_apply(params, dit, lat, t, emb, gd)
    assert rel(got, ref.velocity(g, lat, t, emb, gd)) < tol


def run(traffic: str, seed: int, control: bool = False) -> harness.Outcome:
    cfg = toy_config()
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    cell = harness.Cell(f"toy-flux.{traffic}", 1, cfg, mix, TOY_LIMITS, [], [])
    return harness.run_cell(
        cell, seed, 0.5, False, time.perf_counter(),
        fam=harness.family_for(cfg, check_published=False), control=control)


@pytest.mark.parametrize("traffic", ["backlog", "solo"])
def test_served_flux_dev_is_correct(traffic):
    out = run(traffic, seed=2**31 + 11)
    assert out.compiles_in_window == 0
    assert ("image_gap" in out.checks) == (traffic == "solo")
    assert harness.correct(out.checks, TOY_LIMITS), out.checks


def test_float8_reference_is_not_correct():
    out = run("backlog", seed=5, control=True)
    assert out.checks["latent_update_gap"] > TOY_LIMITS["latent_update_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


def test_the_cell_resolves_at_published_widths():
    from repro.diffusion.config import FLUX_DEV

    cell = harness.resolve(CELL)
    assert cell.config["architecture"] == "flux"
    names = {m["name"] for m in cell.per_layer}
    assert {"double_block_ms.backlog", "single_block_ms.backlog",
            "segment_row_step_ms.backlog", "flash_attn_roofline.backlog",
            "mfu.backlog"} <= names
    # every width as published; only the two block counts are cut
    dit = harness.family_for(cell.config).dit
    assert (dit.n_double, dit.n_single) == (6, 12)
    assert dit == dataclasses.replace(FLUX_DEV.published, n_double=6,
                                      n_single=12)
    assert dit.schedule_shift == pytest.approx(np.exp(1.15))
    with pytest.raises(harness.RunFailure, match="n_double 6 differs"):
        harness.family_for(dict(cell.config, reduced={}))


def test_counts_at_the_stage():
    g = ref.geometry_from_config(stage_config())
    assert ref.rows_per_step(g) == 1
    # 18 blocks of 1.305 TFLOP (1.044 linear, 0.261 attention) and
    # about 0.02 TFLOP of embedders and head
    assert ref.double_flops(g) == pytest.approx(1.305e12, rel=1e-3)
    assert ref.single_flops(g) == pytest.approx(1.305e12, rel=1e-3)
    assert ref.row_step_flops(g) == pytest.approx(23.50e12, rel=1e-3)
    calls = ref.attention_calls(g)
    assert len(calls) == 18
    assert calls[0] == (4.0 * 4608 ** 2 * 3072, 4.0 * 4608 * 3072 * 2)


def test_schedule_matches_the_program():
    from repro.diffusion.config import FLUX_DEV, FLUX_SCHNELL
    from repro.diffusion.sampler import flow_schedule

    g = ref.geometry_from_config(stage_config())
    dit = harness.family_for(stage_config()).dit
    np.testing.assert_allclose(
        ref.flow_schedule(g, 28), flow_schedule(28, dit.schedule_shift),
        rtol=1e-6)
    toy = ref.geometry_from_config(toy_config())
    np.testing.assert_allclose(
        ref.flow_schedule(toy, 4),
        flow_schedule(4, FLUX_DEV.dit.schedule_shift), rtol=1e-6)
    assert FLUX_SCHNELL.dit.schedule_shift == 1.0


def block_readings(double_s: float, single_s: float, steps: int
                   ) -> harness.Readings:
    """One segment dispatch of 2 requests x ``steps`` steps at the stage's
    shapes, its two scan loops and 0.01 s of other work on the device."""
    E = xplane.Event
    cfg = stage_config()
    arch = harness.architecture(cfg)
    g = arch.geometry_from_config(cfg)
    carry = lambda *tokens: ", ".join(f"bf16[2,{t},{g.d_model}]{{2,1,0}}"
                                      for t in tokens)
    ops = [E(f"%while.31 = (s32[], {carry(4096, 512)}, bf16[6,3072,18432]) "
             f"while(%tuple.1), condition=%c.1, body=%b.1", 0.0, double_s,
             program="jit_run"),
           E(f"%while.32 = (s32[], {carry(4608)}, bf16[12,3072,9216]) "
             f"while(%tuple.2), condition=%c.2, body=%b.2", double_s,
             double_s + single_s, program="jit_run"),
           E("%fusion.7 = bf16[2,4608,3072]{2,1,0} fusion()", double_s
             + single_s, double_s + single_s + 0.01, program="jit_run")]
    total = double_s + single_s + 0.01
    dev = xplane.Device("/device:TPU:0", [E("jit_run", 0.0, total)], ops)
    return harness.Readings(
        window_s=total, dispatches=[harness.Dispatch("segment:x", 2, steps)],
        trace=xplane.Trace([dev], []), geometry=g, architecture=arch,
        peaks={"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12},
        programs=harness.PROGRAMS, flash_kernel=harness.FLASH_KERNEL)


def test_block_metrics_read_their_loops():
    r = block_readings(double_s=1.2, single_s=2.4, steps=1)
    double = harness.read_metric("double_block_ms.backlog", r)
    single = harness.read_metric("single_block_ms.backlog", r)
    # 2 request-steps of one row: 1.2 s over 6 blocks, 2.4 s over 12
    assert double == pytest.approx(1e3 * 1.2 / (6 * 2))
    assert single == pytest.approx(1e3 * 2.4 / (12 * 2))
    whole = harness.read_metric("segment_row_step_ms.backlog", r)
    assert 6 * double + 12 * single == pytest.approx(whole - 1e3 * 0.01 / 2)


def test_block_metrics_find_nothing_in_an_mmdit_cell():
    r = block_readings(1.0, 1.0, 1)
    cfg = json.loads((BENCH / "configs" / "sd3-medium.json").read_text())
    arch = harness.architecture(cfg)
    r.geometry, r.architecture = arch.geometry_from_config(cfg), arch
    assert harness.read_metric("double_block_ms.backlog", r) is None
    assert harness.read_metric("single_block_ms.backlog", r) is None
