"""A whole run of a cell at toy width on the CPU, past the harness's look
for a chip: the sound program is correct, the control (the program's own
int8 path) is not, and neither is a timed path broken underneath in each
way a served one-chip cell can be broken."""

import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import harness

BENCH = Path(__file__).resolve().parents[1]
# toy readings on the CPU: text_embed_gap about 5e-7 (f32, CPU matmuls),
# latent_update_gap 0.012 over a 6-step chain and 0.009 over 28 steps,
# image_gap 0.008; the program's int8 path reads 0.027 on the text
# embedding, the float8 reference 0.08-0.1 on every number
TOY_LIMITS = {"text_embed_gap": 1e-4, "latent_update_gap": 0.1,
              "image_gap": 0.05}


def toy_cell(traffic: str) -> harness.Cell:
    cfg = json.loads((BENCH / "configs" / "sd3-medium.json").read_text())
    cfg.update(sample_size=16, in_channels=4, out_channels=4, num_layers=2,
               attention_head_dim=16, num_attention_heads=4,
               joint_attention_dim=64, text_tokens=8, batch_cap=4)
    cfg["text_encoder"] = dict(cfg["text_encoder"], width=64)
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return harness.Cell(f"toy.{traffic}", 1, cfg, mix, TOY_LIMITS, [], [])


def run(traffic: str, seed: int = 1) -> harness.Outcome:
    cell = toy_cell(traffic)
    return harness.run_cell(
        cell, seed, 0.5, False, time.perf_counter(),
        fam=harness.family_for(cell.config, check_published=False))


@pytest.mark.parametrize("traffic", ["backlog", "solo"])
def test_sound_program_is_correct(traffic):
    out = run(traffic, seed=2**31 + 7)
    assert out.compiles_in_window == 0
    assert set(out.checks) >= {"text_embed_gap", "latent_update_gap"}
    assert ("image_gap" in out.checks) == (traffic == "solo")
    assert harness.correct(out.checks, TOY_LIMITS), out.checks


def test_check_runs_the_architecture_the_config_names(monkeypatch):
    arch = harness.architecture(toy_cell("backlog").config)
    assert Path(arch.__file__) == BENCH / "reference" / "mmdit.py"
    real, calls = arch.sample, []

    def counted(*args, **kwargs):
        calls.append(args[5:7])             # the chain's start and stop
        return real(*args, **kwargs)

    monkeypatch.setattr(arch, "sample", counted)
    out = run("backlog", seed=2**33 + 1)
    assert len(calls) == 2                  # one per followed request
    assert harness.correct(out.checks, TOY_LIMITS), out.checks


def test_control_is_not_correct():
    from repro.nn.layers import set_quant_mode

    prev = set_quant_mode("int8")
    try:
        out = run("backlog")
    finally:
        set_quant_mode(prev)
    assert out.checks["text_embed_gap"] > TOY_LIMITS["text_embed_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


@pytest.mark.parametrize("traffic", ["backlog", "solo"])
def test_reference_control_is_not_correct(traffic):
    # the reference computed in float8, put in the program's place
    cell = toy_cell(traffic)
    out = harness.run_cell(
        cell, 3, 0.5, False, time.perf_counter(),
        fam=harness.family_for(cell.config, check_published=False),
        control=True)
    assert out.checks["latent_update_gap"] > TOY_LIMITS["latent_update_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


def _segment_fault(monkeypatch, fault):
    from repro.diffusion.ops import DenoiseSegment

    real = DenoiseSegment.execute_batch

    def broken(self, comps, batch_kwargs):
        outs = real(self, comps, batch_kwargs)
        return fault(batch_kwargs, outs)

    monkeypatch.setattr(DenoiseSegment, "execute_batch", broken)


def test_state_left_unchanged(monkeypatch):
    _segment_fault(monkeypatch, lambda kws, outs: [
        {"latents": kw["latents"]} for kw in kws])
    out = run("backlog")
    assert out.checks["latent_update_gap"] > 0.9
    assert not harness.correct(out.checks, TOY_LIMITS)


def test_half_of_the_batch_left_out(monkeypatch):
    # the second half of the batch gets the first half's results
    def half(kws, outs):
        h = len(outs) // 2
        return outs[:h] + [outs[i % h] for i in range(h, len(outs))] if h else outs

    _segment_fault(monkeypatch, half)
    out = run("backlog")
    assert not harness.correct(out.checks, TOY_LIMITS)


@pytest.mark.parametrize("traffic", ["backlog", "solo"])
def test_answer_altered_where_produced(monkeypatch, traffic):
    # one request's update made 20 % longer where the segment produces it
    def nudge(kws, outs):
        outs = list(outs)
        new, old = outs[-1]["latents"], kws[-1]["latents"]
        outs[-1] = {"latents": new + 0.2 * (new - old)}
        return outs

    _segment_fault(monkeypatch, nudge)
    out = run(traffic)
    assert out.checks["latent_update_gap"] > TOY_LIMITS["latent_update_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


def test_prompt_embedding_altered(monkeypatch):
    from repro.diffusion.ops import TextEncoder

    real = TextEncoder.execute_batch

    def first_token_dropped(self, comps, batch_kwargs):
        return [{"prompt_embeds": o["prompt_embeds"].at[:, 0].set(0.0)}
                for o in real(self, comps, batch_kwargs)]

    monkeypatch.setattr(TextEncoder, "execute_batch", first_token_dropped)
    out = run("backlog")
    assert out.checks["text_embed_gap"] > TOY_LIMITS["text_embed_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


def test_image_altered(monkeypatch):
    from repro.diffusion.ops import VAEDecode

    real = VAEDecode.execute_batch

    def brighter(self, comps, batch_kwargs):
        return [{"image": jnp.clip(o["image"] + 0.05, -1, 1)}
                for o in real(self, comps, batch_kwargs)]

    monkeypatch.setattr(VAEDecode, "execute_batch", brighter)
    out = run("solo")
    assert out.checks["image_gap"] > TOY_LIMITS["image_gap"]
    assert not harness.correct(out.checks, TOY_LIMITS)


def test_a_missing_limit_is_not_correct():
    assert not harness.correct({"latent_update_gap": 0.0}, {})
    assert not harness.correct({"latent_update_gap": float("nan")},
                               TOY_LIMITS)
