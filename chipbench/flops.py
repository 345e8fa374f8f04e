"""Operations and bytes of the served programs, counted from shapes.

A multiply-add counts 2 FLOPs.  Elementwise work (norms, modulation,
softmax, activations) is left out: it is under 1 % of any count here.
"""

from __future__ import annotations

from chipbench.reference.mmdit import Geometry


def mmdit_layer_flops(g: Geometry) -> dict:
    """One MMDiT block for one CFG row: the dense projections of the
    image and text streams (q, k, v, o and the two MLP matmuls) and the
    joint attention (QK^T and PV over all tokens)."""
    d, ff = g.d_model, g.d_ff
    per_token = 2 * (4 * d * d + 2 * d * ff)
    ada = 2 * 2 * d * 6 * d                 # both streams, once per row
    return {"image": per_token * g.image_tokens,
            "text": per_token * g.text_tokens,
            "attention": flash_attn_flops(g),
            "ada": ada}


def flash_attn_flops(g: Geometry) -> float:
    """QK^T and PV of one joint attention call for one row, unpadded."""
    return 4.0 * g.tokens * g.tokens * g.d_model


def flash_attn_bytes(g: Geometry, itemsize: int = 2) -> float:
    """Least HBM traffic of that call: q, k, v read once, o written once."""
    return 4.0 * g.tokens * g.d_model * itemsize


def mmdit_row_step_flops(g: Geometry) -> float:
    """One backbone forward of one CFG row: every block, the patch,
    text and timestep embeddings and the final adaLN and head."""
    layer = sum(mmdit_layer_flops(g).values())
    d = g.d_model
    embed = 2 * (g.image_tokens * g.in_dim * d + g.text_tokens * g.text_dim * d
                 + 256 * d + d * d)
    head = 2 * (d * 2 * d + g.image_tokens * d * g.in_dim)
    return g.n_layers * layer + embed + head


def request_step_flops(g: Geometry) -> float:
    """One denoising step of one request: both CFG rows."""
    return 2 * mmdit_row_step_flops(g)


def text_encoder_flops(g: Geometry) -> float:
    """The stand-in encoder over one prompt (text_tokens positions)."""
    d, s = g.text_dim, g.text_tokens
    dense = 2 * s * (4 * d * d + 2 * d * 4 * d)
    attn = 4.0 * s * s * d
    return g.te_layers * (dense + attn)


def vae_decode_flops(g: Geometry) -> float:
    """The stand-in decoder for one image: 1x1 convolution at latent
    resolution, then three 3x3 convolutions at 2x, 4x and 8x."""
    s, b, c = g.latent_size, g.vae_base, g.latent_channels
    out = 2.0 * s * s * c * 2 * b
    widths = [(2 * b, 2 * b), (2 * b, b), (b, 3)]
    for i, (cin, cout) in enumerate(widths):
        side = s * 2 ** (i + 1)
        out += 2.0 * side * side * 9 * cin * cout
    return out


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of compute and memory."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
