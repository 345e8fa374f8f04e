"""A new backbone architecture joins the benchmark as new files only: in a
copy of ``BENCHMARK.json`` and ``chipbench/``, a toy architecture's
reference module, configuration and limits, and its entries in
``BENCHMARK.json``, are found by name, checked and counted, while every
file the copy already had stays as it was."""

import json
import shutil
import textwrap
from pathlib import Path

import numpy as np
import pytest

from chipbench import flops, harness, traffic, xplane

REPO = Path(__file__).resolve().parents[2]

TOYARCH = '''
"""A toy backbone: one row per request-step (guidance-distilled), two
attention calls of its own per row-step, and a sampler that moves the
latents by 0.5 per step."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

REDUCIBLE = {"depth": "n_layers"}
SAMPLED = []


@dataclasses.dataclass(frozen=True)
class Geometry:
    family: str
    depth: int
    text_dim: int
    text_tokens: int
    latent_size: int
    latent_channels: int
    te_vocab: int
    te_layers: int
    te_heads: int
    te_dtype: str
    vae_base: int
    vae_dtype: str


def geometry_from_config(cfg):
    te, vae = cfg["text_encoder"], cfg["vae"]
    return Geometry(cfg["family"], cfg["depth"], cfg["text_dim"],
                    cfg["text_tokens"], cfg["latent_size"],
                    cfg["latent_channels"], te["vocab"], te["layers"],
                    te["heads"], te["dtype"], vae["base"], vae["dtype"])


def initial_latents(g, seed):
    return jnp.full((1, g.latent_size, g.latent_size, g.latent_channels),
                    float(seed % 5 + 1))


def sample(g, lat, emb, steps, guidance, start=0, stop=None, fp8=False):
    SAMPLED.append((start, stop, fp8))
    return lat + 0.5 * (stop - start)


def rows_per_step(g):
    return 1


def row_step_flops(g):
    return 3e12


def attention_calls(g):
    return [(4e10, 1e7), (1e10, 2e9)]


def program_fields(g):
    return {"n_layers": g.depth, "text_tokens": g.text_tokens}
'''

TOY_CONFIG = {
    "name": "toy-arch", "architecture": "toyarch", "family": "sd3",
    "depth": 3, "text_dim": 32, "text_tokens": 333, "latent_size": 4,
    "latent_channels": 2, "steps": 4, "guidance": 1.0, "batch_cap": 4,
    "text_encoder": {"vocab": 64, "layers": 1, "heads": 2,
                     "dtype": "float32"},
    "vae": {"base": 4, "dtype": "float32"},
    "reduced": {"depth": {"published": 24, "here": 3}},
}
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12}


def add_cell(root: Path, config: dict) -> str:
    """A configuration file, its limits and its entries in
    ``BENCHMARK.json``, in the copy at ``root``; the cell's name."""
    name = config["name"]
    cell = f"{name}.backlog"
    (root / "chipbench" / "configs" / f"{name}.json").write_text(
        json.dumps(config))
    (root / "chipbench" / "limits" / f"{cell}.json").write_text(
        json.dumps({"text_embed_gap": 1e-4, "latent_update_gap": 1e-3}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "a test",
                            "file": f"chipbench/configs/{name}.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": "backlog", "chips": 1,
                              "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted((root / "chipbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark with the toy architecture added, and the
    copy's files from before the addition."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files(tmp_path)
    (tmp_path / "chipbench" / "reference" / "toyarch.py").write_text(
        textwrap.dedent(TOYARCH))
    cell = add_cell(tmp_path, TOY_CONFIG)
    return tmp_path, cell, before


def test_only_new_files_were_added(tree):
    root, _, before = tree
    after = files(root)
    assert {k: after[k] for k in before} == before
    assert sorted(map(str, set(after) - set(before))) == [
        "chipbench/configs/toy-arch.json",
        "chipbench/limits/toy-arch.backlog.json",
        "chipbench/reference/toyarch.py"]


def test_the_cell_resolves_to_the_toy_module(tree):
    root, cell, _ = tree
    c = harness.resolve(cell, root=root)
    arch = harness.architecture(c.config, root)
    assert Path(arch.__file__) == root / "chipbench/reference/toyarch.py"
    assert arch.rows_per_step(arch.geometry_from_config(c.config)) == 1
    assert set(c.limits) == {"text_embed_gap", "latent_update_gap"}
    # the program's family takes the toy's fields over sd3's published
    # geometry, and a field the cell did not reduce is checked
    fam = harness.family_for(c.config, root=root)
    assert (fam.dit.n_layers, fam.dit.d_model) == (3, 1536)
    with pytest.raises(harness.RunFailure, match="n_layers 3 differs"):
        harness.family_for(dict(c.config, reduced={}), root=root)


def test_compare_samples_with_the_toy_module(tree):
    from chipbench.reference import standins

    root, cell, _ = tree
    c = harness.resolve(cell, root=root)
    arch = harness.architecture(c.config, root)
    g = arch.geometry_from_config(c.config)
    prompts = ["a red fox", "an owl at dusk"]
    emb = list(standins.as_numpy(standins.encode(g, prompts)))
    lat = np.ones((1, 4, 4, 2))
    chains = [
        harness.Row(1, 7, prompts[0], 1, 2, lat, lat + 1.0),
        # from the noise: the toy's initial latents for seed 8 are all 4
        harness.Row(2, 8, prompts[1], 0, 1, None, np.full((1, 4, 4, 2), 4.5)),
    ]
    arch.SAMPLED.clear()
    checks = harness.compare(arch, g, traffic.mix_from({"loop": "closed",
                             "clients": 2, "prompt_words": [2, 4]}, c.config),
                             harness.Picked(prompts, emb, chains, [None, None]))
    assert arch.SAMPLED == [(1, 3, False), (0, 1, False)]
    assert checks["text_embed_gap"] == 0.0
    assert checks["latent_update_gap"] == pytest.approx(0.0, abs=1e-6)
    assert harness.correct(checks, c.limits)


def toy_readings(root: Path, cell: str) -> harness.Readings:
    """Two segment dispatches of 2 requests x 3 steps and one text
    encoder dispatch of 2 prompts; on the device, the segment program
    ran 1.2 s and the flash kernel 0.8 s of it."""
    c = harness.resolve(cell, root=root)
    arch = harness.architecture(c.config, root)
    E = xplane.Event
    dev = xplane.Device(
        "/device:TPU:0", [E("jit_run", 0.0, 0.6), E("jit_run", 1.0, 1.6)],
        [E("%mha.5 = bf16[2,333,64]{2,1,0} custom-call(), "
           "custom_call_target=\"tpu_custom_call\"", a, a + 0.4,
           program="jit_run") for a in (0.1, 1.1)])
    return harness.Readings(
        window_s=2.0,
        dispatches=[harness.Dispatch("segment:sd3", 2, 3)] * 2
        + [harness.Dispatch("text_encoder:sd3", 2, 1)],
        trace=xplane.Trace([dev], []),
        geometry=arch.geometry_from_config(c.config), architecture=arch,
        peaks=PEAKS, programs=harness.PROGRAMS,
        flash_kernel=harness.FLASH_KERNEL)


def test_counts_and_metrics_use_the_toy_rows_and_calls(tree):
    root, cell, _ = tree
    r = toy_readings(root, cell)
    assert r.request_steps() == 12
    # one row per request-step, 3e12 each, and the stand-in encoder
    assert r.flops() == pytest.approx(
        12 * 1 * 3e12 + 2 * flops.text_encoder_flops(r.geometry))
    # 1.2 s of the segment program over 12 request-steps of one row
    assert harness.read_metric("segment_row_step_ms.backlog", r, root) == \
        pytest.approx(100.0)
    # per row-step: max(4e10 / 1e15, 1e7 / 1e12) + max(1e10 / 1e15,
    # 2e9 / 1e12) = 4e-5 + 2e-3 s; 12 row-steps over 0.8 s of the kernel
    assert harness.read_metric("flash_attn_roofline.backlog", r, root) == \
        pytest.approx(100.0 * 2.04e-3 * 12 / 0.8)


@pytest.mark.parametrize("architecture,error", [
    (None, "names no architecture"),
    ("no_such_arch", "has no reference module"),
    ("../reference/mmdit", "names no architecture"),
])
def test_a_config_without_a_known_architecture_fails_in_resolve(
        tmp_path, architecture, error):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = {k: v for k, v in TOY_CONFIG.items() if k != "architecture"}
    if architecture is not None:
        config["architecture"] = architecture
    cell = add_cell(tmp_path, config)
    with pytest.raises(ValueError, match=error):
        harness.resolve(cell, root=tmp_path)
