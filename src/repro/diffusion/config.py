"""Diffusion model family configs (§7.1, Table 2).

Each family carries:

* ``real-scale`` statistics — parameter counts of the published
  checkpoints, used with ``published`` by the analytic latency profiles,
  the monolithic baselines, and the roofline analysis;
* ``published`` — the backbone geometry of the published checkpoint: an
  MMDiT's full :class:`DiTConfig` or a FLUX backbone's
  :class:`FluxConfig`, in bf16, which
  :meth:`DiffusionFamily.at_published_width` swaps in for ``dit`` to serve
  the family at its real width, or a :class:`UNetGeometry` for a UNet
  (SDXL), which only the cost model reads;
* ``dit`` — the backbone configuration the executable plane runs: the
  CPU-sized toy by default, used by the correctness tests.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple, Union

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

    architecture = "mmdit"
    schedule_shift = 1.0

    @property
    def adapter_layers(self) -> int:
        """Blocks whose image stream carries LoRA targets."""
        return self.n_layers


@dataclasses.dataclass(frozen=True)
class FluxConfig:
    """Architecture of a FLUX.1 backbone (``FluxTransformer2DModel``):
    ``n_double`` two-stream blocks, then ``n_single`` single-stream
    parallel blocks over the joined [text; image] sequence, with 3-axis
    RoPE on q and k, QK RMSNorm, and the time, guidance and pooled-text
    embedders feeding every block's modulation."""

    d_model: int
    n_double: int
    n_single: int
    n_heads: int
    d_ff: int
    text_dim: int
    pooled_dim: int           # width of the pooled text vector (CLIP-L)
    latent_size: int          # latent spatial resolution (square)
    latent_channels: int
    patch: int
    text_tokens: int
    rope_axes: Tuple[int, ...]     # head_dim split over (text, row, col)
    rope_theta: float
    guidance_embed: bool      # guidance is a backbone input (dev), not CFG
    time_shift: bool          # schedule shifted by exp(mu(image tokens))
    dtype: object = jnp.float32

    architecture = "flux"

    @property
    def n_layers(self) -> int:
        return self.n_double + self.n_single

    @property
    def adapter_layers(self) -> int:
        return self.n_double

    @property
    def image_tokens(self) -> int:
        return (self.latent_size // self.patch) ** 2

    @property
    def tokens(self) -> int:
        return self.image_tokens + self.text_tokens

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def in_dim(self) -> int:
        return self.patch * self.patch * self.latent_channels

    @property
    def schedule_shift(self) -> float:
        """exp(mu) of BFL's ``get_schedule``: mu interpolated linearly in
        the image token count from 0.5 at 256 tokens to 1.15 at 4096;
        1 (no shift) for a model sampled unshifted (schnell)."""
        if not self.time_shift:
            return 1.0
        mu = 0.5 + (1.15 - 0.5) / (4096 - 256) * (self.image_tokens - 256)
        return math.exp(mu)

    @property
    def n_params(self) -> int:
        """Parameters, counted from the shapes of ``init_flux``."""
        d, ff, hd = self.d_model, self.d_ff, self.head_dim
        lin = lambda i, o: i * o + o
        double = 2 * (lin(d, 6 * d) + 3 * lin(d, d) + 2 * hd + lin(d, d)
                      + lin(d, ff) + lin(ff, d))
        single = lin(d, 3 * d) + lin(d, 3 * d + ff) + 2 * hd + lin(d + ff, d)
        mlp = lambda i: lin(i, d) + lin(d, d)
        outer = (lin(self.in_dim, d) + lin(self.text_dim, d) + mlp(256)
                 + mlp(self.pooled_dim) + lin(d, 2 * d) + lin(d, self.in_dim)
                 + (mlp(256) if self.guidance_embed else 0))
        return self.n_double * double + self.n_single * single + outer


@dataclasses.dataclass(frozen=True)
class UNetGeometry:
    """Width, depth and token counts of a UNet backbone, which has no
    MMDiT config here; the cost model reads the same fields as a
    :class:`DiTConfig`'s."""

    d_model: int
    n_layers: int
    image_tokens: int
    text_tokens: int

    @property
    def adapter_layers(self) -> int:
        return self.n_layers


_TOY = DiTConfig(
    d_model=64, n_layers=2, n_heads=4, d_ff=256, text_dim=64,
    latent_size=16, latent_channels=4, patch=2, text_tokens=8,
)
_TOY_FLUX = FluxConfig(
    d_model=64, n_double=1, n_single=2, n_heads=4, d_ff=256, text_dim=64,
    pooled_dim=16, latent_size=16, latent_channels=4, patch=2,
    text_tokens=8, rope_axes=(4, 6, 6), rope_theta=10000.0,
    guidance_embed=True, time_shift=True,
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
    published: Union[DiTConfig, FluxConfig, UNetGeometry]
    dit: Union[DiTConfig, FluxConfig] = _TOY     # executable backbone config

    @property
    def image_tokens(self) -> int:    # 1024px -> 4096 (patch-2 on /8 VAE)
        return self.published.image_tokens

    @property
    def text_tokens(self) -> int:
        return self.published.text_tokens

    def at_published_width(self) -> "DiffusionFamily":
        """This family with its published backbone geometry as the
        executable config (same name, so workflows and model ids match)."""
        if not isinstance(self.published, (DiTConfig, FluxConfig)):
            raise ValueError(f"{self.name}: no published backbone geometry")
        return dataclasses.replace(self, dit=self.published)

    @property
    def cfg_factor(self) -> float:
        return 2.0 if self.uses_cfg else 1.0

    def served_backbone_params(self) -> float:
        """Parameters of the backbone as served: a FLUX pipeline stage (the
        published geometry with blocks cut, as a one-chip cell serves it)
        holds its own count; otherwise the published model's, which the
        CPU-sized toy stands in for."""
        dit, pub = self.dit, self.published
        if isinstance(dit, FluxConfig) and dataclasses.replace(
                dit, n_double=pub.n_double, n_single=pub.n_single) == pub:
            return float(dit.n_params)
        return self.backbone_params

    def backbone_step_flops(self) -> float:
        """FLOPs of ONE denoising step per request (incl. CFG passes)."""
        tokens = self.image_tokens + self.text_tokens
        return (2.0 * self.served_backbone_params() * tokens
                * self.cfg_factor)

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
        return self.served_backbone_params() * 2.0     # bf16 weights

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
# latents, patch 2 -> 4096 image tokens).
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
# FLUX.1-dev's transformer/config.json (19 double- and 38 single-stream
# blocks, 24 heads of 128, axes_dims_rope [16, 56, 56], guidance_embeds)
# and its pipeline defaults (512 T5 tokens, a dynamically shifted schedule)
FLUX_DEV_DIT = FluxConfig(
    d_model=3072, n_double=19, n_single=38, n_heads=24, d_ff=12288,
    text_dim=4096, pooled_dim=768, latent_size=128, latent_channels=16,
    patch=2, text_tokens=512, rope_axes=(16, 56, 56), rope_theta=10000.0,
    guidance_embed=True, time_shift=True, dtype=jnp.bfloat16,
)
# FLUX.1-schnell: the same blocks, no guidance embedder, unshifted schedule
FLUX_SCHNELL_DIT = dataclasses.replace(
    FLUX_DEV_DIT, guidance_embed=False, time_shift=False)

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
    backbone_params=float(FLUX_DEV_DIT.n_params),
    text_encoder_params=4.9e9,      # CLIP-L + T5-XXL
    vae_params=8.4e7,
    controlnet_params=0.72e9,       # ~6% of base (paper §7.3)
    denoise_steps=28,
    uses_cfg=False,                 # guidance-distilled
    published=FLUX_DEV_DIT,
    dit=_TOY_FLUX,
)

FLUX_SCHNELL = DiffusionFamily(
    name="flux-schnell",
    backbone_params=float(FLUX_SCHNELL_DIT.n_params),
    text_encoder_params=4.9e9,
    vae_params=8.4e7,
    controlnet_params=0.72e9,
    denoise_steps=4,                # timestep-distilled
    uses_cfg=False,
    published=FLUX_SCHNELL_DIT,
    dit=dataclasses.replace(_TOY_FLUX, guidance_embed=False,
                            time_shift=False),
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
