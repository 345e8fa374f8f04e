"""Diffusion model family configs (§7.1, Table 2).

Each family carries:

* ``real-scale`` statistics — parameter counts of the published
  checkpoints, used with ``published`` by the analytic latency profiles,
  the monolithic baselines, and the roofline analysis;
* ``published`` — the backbone geometry of the published checkpoint: an
  MMDiT's full :class:`DiTConfig` in bf16, which
  :meth:`DiffusionFamily.at_published_width` swaps in for ``dit`` to serve
  the family at its real width, or a :class:`UNetGeometry` for a UNet
  (SDXL), which only the cost model reads;
* ``dit`` — the backbone configuration the executable plane runs: the
  CPU-sized toy by default, used by the correctness tests.
"""

from __future__ import annotations

import dataclasses
from typing import Union

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    """Architecture of an MMDiT backbone (also used for ControlNet branches)."""

    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    text_dim: int
    latent_size: int          # latent spatial resolution (square)
    latent_channels: int
    patch: int
    text_tokens: int
    dtype: object = jnp.float32

    @property
    def image_tokens(self) -> int:
        return (self.latent_size // self.patch) ** 2

    @property
    def tokens(self) -> int:
        return self.image_tokens + self.text_tokens

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


@dataclasses.dataclass(frozen=True)
class UNetGeometry:
    """Width, depth and token counts of a UNet backbone, which has no
    MMDiT config here; the cost model reads the same fields as a
    :class:`DiTConfig`'s."""

    d_model: int
    n_layers: int
    image_tokens: int
    text_tokens: int


_TOY = DiTConfig(
    d_model=64, n_layers=2, n_heads=4, d_ff=256, text_dim=64,
    latent_size=16, latent_channels=4, patch=2, text_tokens=8,
)


@dataclasses.dataclass(frozen=True)
class DiffusionFamily:
    """One base-model family from Table 2 (SD3, SD3.5-Large, Flux-*)."""

    name: str
    backbone_params: float        # real-scale parameter count
    text_encoder_params: float    # aggregate (CLIP-L/G + T5-XXL where used)
    vae_params: float
    controlnet_params: float
    denoise_steps: int
    uses_cfg: bool                # classifier-free guidance (2 passes/step)
    published: Union[DiTConfig, UNetGeometry]   # published backbone geometry
    dit: DiTConfig = _TOY         # executable backbone config

    @property
    def image_tokens(self) -> int:    # 1024px -> 4096 (patch-2 on /8 VAE)
        return self.published.image_tokens

    @property
    def text_tokens(self) -> int:
        return self.published.text_tokens

    def at_published_width(self) -> "DiffusionFamily":
        """This family with its published backbone geometry as the
        executable config (same name, so workflows and model ids match)."""
        if not isinstance(self.published, DiTConfig):
            raise ValueError(f"{self.name}: no published MMDiT geometry")
        return dataclasses.replace(self, dit=self.published)

    @property
    def cfg_factor(self) -> float:
        return 2.0 if self.uses_cfg else 1.0

    def backbone_step_flops(self) -> float:
        """FLOPs of ONE denoising step per request (incl. CFG passes)."""
        tokens = self.image_tokens + self.text_tokens
        return 2.0 * self.backbone_params * tokens * self.cfg_factor

    def controlnet_step_flops(self) -> float:
        tokens = self.image_tokens + self.text_tokens
        return 2.0 * self.controlnet_params * tokens * self.cfg_factor

    def text_encode_flops(self) -> float:
        return 2.0 * self.text_encoder_params * self.text_tokens

    def vae_decode_flops(self) -> float:
        # conv decoder over the full pixel grid; ~2 orders above param count
        return 2.5e12 * (self.image_tokens / 4096.0)

    # ------------------------------------------------------------- bytes
    def backbone_bytes(self) -> float:
        return self.backbone_params * 2.0          # fp16/bf16 weights

    def text_encoder_bytes(self) -> float:
        return self.text_encoder_params * 2.0

    def vae_bytes(self) -> float:
        return self.vae_params * 2.0

    def controlnet_bytes(self) -> float:
        return self.controlnet_params * 2.0

    def workflow_footprint(self) -> float:
        return self.backbone_bytes() + self.text_encoder_bytes() + self.vae_bytes()

    def latent_bytes(self) -> float:
        # latent tensor (e.g. 128x128x16 fp16)
        g = self.published
        return self.image_tokens * 4 * g.d_model / g.n_layers  # ~0.5-2MB

    def controlnet_residual_bytes(self) -> float:
        """Residual feature maps transferred per denoising step."""
        g = self.published
        inj_layers = max(1, g.n_layers // 2)
        return inj_layers * (g.image_tokens + g.text_tokens) * g.d_model * 2.0


# Published backbone geometries in bf16: 1024px images (128x128x16
# latents, patch 2 -> 4096 image tokens).  The two-stream MMDiT block
# slightly over-parameterizes Flux's mixed joint/single-stream stack.
SD3_DIT = DiTConfig(
    d_model=1536, n_layers=24, n_heads=24, d_ff=6144, text_dim=4096,
    latent_size=128, latent_channels=16, patch=2, text_tokens=333,
    dtype=jnp.bfloat16,
)
SD35_LARGE_DIT = DiTConfig(
    d_model=2432, n_layers=38, n_heads=38, d_ff=9728, text_dim=4096,
    latent_size=128, latent_channels=16, patch=2, text_tokens=333,
    dtype=jnp.bfloat16,
)
FLUX_DIT = DiTConfig(
    d_model=3072, n_layers=57, n_heads=24, d_ff=12288, text_dim=4096,
    latent_size=128, latent_channels=16, patch=2, text_tokens=512,
    dtype=jnp.bfloat16,
)

SD3 = DiffusionFamily(
    name="sd3",
    backbone_params=2.0e9,
    text_encoder_params=5.5e9,      # CLIP-L + CLIP-G + T5-XXL
    vae_params=8.4e7,
    controlnet_params=1.0e9,
    denoise_steps=28,
    uses_cfg=True,
    published=SD3_DIT,
)

SD35_LARGE = DiffusionFamily(
    name="sd3.5-large",
    backbone_params=8.1e9,
    text_encoder_params=5.5e9,
    vae_params=8.4e7,
    controlnet_params=2.5e9,
    denoise_steps=40,
    uses_cfg=True,
    published=SD35_LARGE_DIT,
)

FLUX_DEV = DiffusionFamily(
    name="flux-dev",
    backbone_params=12.0e9,
    text_encoder_params=4.9e9,      # CLIP-L + T5-XXL
    vae_params=8.4e7,
    controlnet_params=0.72e9,       # ~6% of base (paper §7.3)
    denoise_steps=28,
    uses_cfg=False,                 # guidance-distilled
    published=FLUX_DIT,
)

FLUX_SCHNELL = DiffusionFamily(
    name="flux-schnell",
    backbone_params=12.0e9,
    text_encoder_params=4.9e9,
    vae_params=8.4e7,
    controlnet_params=0.72e9,
    denoise_steps=4,                # timestep-distilled
    uses_cfg=False,
    published=FLUX_DIT,
)

FAMILIES = {f.name: f for f in (SD3, SD35_LARGE, FLUX_DEV, FLUX_SCHNELL)}

# SDXL appears in the paper's §7.4 case studies (approximate caching, async
# LoRA); UNet-based, but for serving purposes only the costs matter.
SDXL = DiffusionFamily(
    name="sdxl",
    backbone_params=2.6e9,
    text_encoder_params=0.8e9,
    vae_params=8.4e7,
    controlnet_params=1.25e9,
    denoise_steps=30,
    uses_cfg=True,
    published=UNetGeometry(d_model=1280, n_layers=70, image_tokens=4096,
                           text_tokens=77),
)
FAMILIES["sdxl"] = SDXL
