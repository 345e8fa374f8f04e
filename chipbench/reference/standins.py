"""The repository's stand-in text encoder and VAE decoder, in float32 at
highest precision.

Tokenizer: each lower-cased word maps to ``crc32(word) % (vocab - 2) + 2``;
id 1 starts the prompt, id 0 pads it to ``text_tokens``.

Text encoder: token + position embeddings, then per layer a pre-RMSNorm
bidirectional attention block and a pre-RMSNorm GELU MLP (4x width), and
a final RMSNorm.

VAE decoder: a 1x1 convolution from the latent channels to 2 * base, then
three stages of (nearest 2x upsampling, 3x3 convolution), widths 2 * base,
base and 3, SiLU between stages and tanh at the end.
"""

from __future__ import annotations

import math
import zlib
from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.mmdit import F32, Geometry, matmul, model_key, rms_norm


def token_ids(prompt: str, vocab: int, max_len: int) -> List[int]:
    ids = [zlib.crc32(w.encode("utf-8")) % (vocab - 2) + 2
           for w in prompt.lower().split()][: max_len - 1]
    ids = [1] + ids
    return ids + [0] * (max_len - len(ids))


def _normal(key, shape, scale, dtype: str) -> jax.Array:
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype).astype(F32)


@partial(jax.jit, static_argnums=(1,))
def text_encoder_weights(key, g: Geometry):
    d, n = g.text_dim, g.te_layers
    ks = list(jax.random.split(key, 3 + n))
    layers = []
    for i in range(n):
        lk = list(jax.random.split(ks[3 + i], 5))
        mk = list(jax.random.split(lk[4], 2))
        w = {name: _normal(lk[j], (d, d), 1 / math.sqrt(d), g.te_dtype)
             for j, name in enumerate(("wq", "wk", "wv", "wo"))}
        w["w1"] = _normal(mk[0], (d, 4 * d), 1 / math.sqrt(d), g.te_dtype)
        w["w2"] = _normal(mk[1], (4 * d, d), 1 / math.sqrt(4 * d), g.te_dtype)
        layers.append(w)
    return {"tok": _normal(ks[0], (g.te_vocab, d), 0.02, g.te_dtype),
            "pos": _normal(ks[1], (g.text_tokens, d), 0.02, g.te_dtype),
            "layers": layers}


@partial(jax.jit, static_argnums=(2, 3))
def _encode(w, ids, g: Geometry, fp8: bool):
    x = w["tok"][ids] + w["pos"][None, : ids.shape[1]]
    b, s, d = x.shape
    hd = d // g.te_heads
    for p in w["layers"]:
        h = rms_norm(x)
        q, k, v = (matmul(h, p[n], fp8).reshape(b, s, g.te_heads, hd)
                   for n in ("wq", "wk", "wv"))
        a = jax.nn.softmax(
            jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd), axis=-1)
        x = x + matmul(jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, s, d),
                       p["wo"], fp8)
        x = x + matmul(jax.nn.gelu(matmul(rms_norm(x), p["w1"], fp8),
                                   approximate=True), p["w2"], fp8)
    return rms_norm(x)


def encode(g: Geometry, prompts: Sequence[str], fp8: bool = False
           ) -> jax.Array:
    """Prompt embeddings [B, text_tokens, text_dim], float32; with
    ``fp8`` the layers' projections computed in float8 (the control)."""
    ids = jnp.asarray([token_ids(p, g.te_vocab, g.text_tokens)
                       for p in prompts], jnp.int32)
    with jax.default_matmul_precision("highest"):
        w = text_encoder_weights(model_key(f"text_encoder:{g.family}"), g)
        return _encode(w, ids, g, fp8)


@partial(jax.jit, static_argnums=(1,))
def vae_weights(key, g: Geometry):
    ks = list(jax.random.split(key, 8))
    b = g.vae_base

    def conv(k, kh, cin, cout):
        return _normal(k, (kh, kh, cin, cout), 1 / math.sqrt(kh * kh * cin),
                       g.vae_dtype)

    return {"dec_in": conv(ks[4], 1, g.latent_channels, 2 * b),
            "dec": [conv(ks[5], 3, 2 * b, 2 * b), conv(ks[6], 3, 2 * b, b),
                    conv(ks[7], 3, b, 3)]}


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


@jax.jit
def _decode(w, lat):
    x = _conv(lat.astype(F32), w["dec_in"])
    for i, k in enumerate(w["dec"]):
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        x = _conv(x, k)
        if i < len(w["dec"]) - 1:
            x = jax.nn.silu(x)
    return jnp.tanh(x)


def decode(g: Geometry, lat: jax.Array) -> jax.Array:
    """Image [B, 8S, 8S, 3] in (-1, 1) from latents [B, S, S, C]."""
    with jax.default_matmul_precision("highest"):
        return _decode(vae_weights(model_key(f"vae:{g.family}"), g), lat)


def as_numpy(x) -> np.ndarray:
    return np.asarray(jax.device_get(x), np.float64)
