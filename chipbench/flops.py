"""Operations and bytes of the served programs, counted from shapes: what
every architecture shares (the stand-in encoder and VAE, and the
roofline).  A backbone's counts sit in its reference module
(``chipbench/reference/<architecture>.py``).

A multiply-add counts 2 FLOPs.  Elementwise work (norms, modulation,
softmax, activations) is left out: it is under 1 % of any count here.
``g`` is an architecture's geometry (see ``chipbench/reference``).
"""

from __future__ import annotations

from typing import Any


def text_encoder_flops(g: Any) -> float:
    """The stand-in encoder over one prompt (text_tokens positions)."""
    d, s = g.text_dim, g.text_tokens
    dense = 2 * s * (4 * d * d + 2 * d * 4 * d)
    attn = 4.0 * s * s * d
    return g.te_layers * (dense + attn)


def vae_decode_flops(g: Any) -> float:
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
