"""Shape counts, the peak table and the configurations."""

import dataclasses
import json
from pathlib import Path

import pytest

from chipbench import flops, harness, peaks
from chipbench.reference import mmdit
from chipbench.reference.mmdit import geometry_from_config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def geometry(name):
    return geometry_from_config(config(name))


def test_sd3_row_step_matches_the_hand_count():
    # per layer: 231.9 GFLOP image stream + 18.9 text stream + 120.5 joint
    # attention = 371.3 GFLOP; 8.91 TFLOP over 24 layers
    g = geometry("sd3-medium")
    layer = mmdit.layer_flops(g)
    assert layer["image"] == pytest.approx(231.9e9, rel=1e-3)
    assert layer["text"] == pytest.approx(18.86e9, rel=1e-3)
    assert layer["attention"] == pytest.approx(120.5e9, rel=1e-3)
    body = g.n_layers * (layer["image"] + layer["text"] + layer["attention"])
    assert body == pytest.approx(8.91e12, rel=1e-3)
    # embeddings, adaLN and head add well under 1 %
    assert 1 < mmdit.row_step_flops(g) / body < 1.01


def test_sd35_stage_row_step():
    g = geometry("sd3.5-large-stage")
    assert (g.d_model, g.n_layers, g.n_heads, g.d_ff) == (2432, 19, 38, 9728)
    layer = mmdit.layer_flops(g)
    body = g.n_layers * (layer["image"] + layer["text"] + layer["attention"])
    assert body == pytest.approx(15.57e12, rel=1e-3)
    # attention is 23 % of the backbone's FLOPs here, 32 % in sd3
    assert layer["attention"] * g.n_layers / body == pytest.approx(0.233, abs=5e-3)


def test_flash_attention_roofline_is_compute_bound_at_4429_tokens():
    g = geometry("sd3-medium")
    p = peaks.peaks_for("TPU v5 lite")
    t = flops.roofline_seconds(mmdit.flash_attn_flops(g), mmdit.flash_attn_bytes(g),
                               p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    assert t == pytest.approx(mmdit.flash_attn_flops(g) / 197e12)
    assert mmdit.flash_attn_bytes(g) / 819e9 < t / 5


def test_standin_counts():
    g = geometry("sd3-medium")
    # 2 layers x 333 tokens x 24 d^2 at d = 4096, plus attention
    assert flops.text_encoder_flops(g) == pytest.approx(2.72e11, rel=1e-2)
    assert flops.vae_decode_flops(g) == pytest.approx(1.634e10, rel=1e-2)
    # one request-step of the backbone is both CFG rows
    r = readings("sd3-medium", [harness.Dispatch("segment:sd3", 1, 1)])
    assert r.flops() == 2 * mmdit.row_step_flops(g)


def test_peak_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


# ------------------------------------------ the cells' architecture module

def readings(name, dispatches):
    cfg = config(name)
    arch = harness.architecture(cfg)
    return harness.Readings(
        window_s=1.0, dispatches=dispatches, trace=None,
        geometry=arch.geometry_from_config(cfg), architecture=arch,
        peaks=peaks.peaks_for("TPU v5 lite"), programs=harness.PROGRAMS,
        flash_kernel=harness.FLASH_KERNEL)


@pytest.mark.parametrize("name", ["sd3-medium", "sd3.5-large-stage"])
def test_configurations_resolve_to_mmdit(name):
    arch = harness.architecture(config(name))
    assert Path(arch.__file__) == CONFIGS.parent / "reference" / "mmdit.py"
    assert arch.rows_per_step(geometry(name)) == 2
    assert arch.REDUCIBLE == {"num_layers": "n_layers"}


@pytest.mark.parametrize("name,per_row", [("sd3-medium", 8.917e12),
                                          ("sd3.5-large-stage", 15.58e12)])
def test_one_dispatch_counts_both_cfg_rows(name, per_row):
    # one B=8 one-step segment dispatch: 8 requests x 2 CFG rows
    r = readings(name, [harness.Dispatch("segment:x", 8, 1)])
    assert r.rows_per_step == 2
    assert r.flops() == pytest.approx(8 * 2 * per_row, rel=1e-3)


@pytest.mark.parametrize("name", ["sd3-medium", "sd3.5-large-stage"])
def test_attention_calls_give_one_roofline_per_block(name):
    g = geometry(name)
    p = peaks.peaks_for("TPU v5 lite")
    roof = lambda f, b: flops.roofline_seconds(
        f, b, p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    calls = readings(name, []).attention_calls
    assert len(calls) == g.n_layers
    assert sum(roof(f, b) for f, b in calls) == pytest.approx(
        g.n_layers * roof(mmdit.flash_attn_flops(g), mmdit.flash_attn_bytes(g)),
        rel=1e-12)


@pytest.mark.parametrize("name", ["sd3-medium", "sd3.5-large-stage"])
def test_program_family_at_the_published_geometry(name):
    from repro.diffusion.config import FAMILIES

    cfg = config(name)
    fam = harness.family_for(cfg)
    pub = FAMILIES[cfg["family"]].published
    assert fam.dit == dataclasses.replace(pub, n_layers=cfg["num_layers"])
