"""Shape counts, the peak table and the configurations."""

import json
from pathlib import Path

import pytest

from chipbench import flops, peaks
from chipbench.reference.mmdit import geometry_from_config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def geometry(name):
    return geometry_from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_sd3_row_step_matches_the_hand_count():
    # per layer: 231.9 GFLOP image stream + 18.9 text stream + 120.5 joint
    # attention = 371.3 GFLOP; 8.91 TFLOP over 24 layers
    g = geometry("sd3-medium")
    layer = flops.mmdit_layer_flops(g)
    assert layer["image"] == pytest.approx(231.9e9, rel=1e-3)
    assert layer["text"] == pytest.approx(18.86e9, rel=1e-3)
    assert layer["attention"] == pytest.approx(120.5e9, rel=1e-3)
    body = g.n_layers * (layer["image"] + layer["text"] + layer["attention"])
    assert body == pytest.approx(8.91e12, rel=1e-3)
    # embeddings, adaLN and head add well under 1 %
    assert 1 < flops.mmdit_row_step_flops(g) / body < 1.01


def test_sd35_stage_row_step():
    g = geometry("sd3.5-large-stage")
    assert (g.d_model, g.n_layers, g.n_heads, g.d_ff) == (2432, 19, 38, 9728)
    layer = flops.mmdit_layer_flops(g)
    body = g.n_layers * (layer["image"] + layer["text"] + layer["attention"])
    assert body == pytest.approx(15.57e12, rel=1e-3)
    # attention is 23 % of the backbone's FLOPs here, 32 % in sd3
    assert layer["attention"] * g.n_layers / body == pytest.approx(0.233, abs=5e-3)


def test_flash_attention_roofline_is_compute_bound_at_4429_tokens():
    g = geometry("sd3-medium")
    p = peaks.peaks_for("TPU v5 lite")
    t = flops.roofline_seconds(flops.flash_attn_flops(g), flops.flash_attn_bytes(g),
                               p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    assert t == pytest.approx(flops.flash_attn_flops(g) / 197e12)
    assert flops.flash_attn_bytes(g) / 819e9 < t / 5


def test_standin_counts():
    g = geometry("sd3-medium")
    # 2 layers x 333 tokens x 24 d^2 at d = 4096, plus attention
    assert flops.text_encoder_flops(g) == pytest.approx(2.72e11, rel=1e-2)
    assert flops.vae_decode_flops(g) == pytest.approx(1.634e10, rel=1e-2)
    assert flops.request_step_flops(g) == 2 * flops.mmdit_row_step_flops(g)


def test_peak_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
