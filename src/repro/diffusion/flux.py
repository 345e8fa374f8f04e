"""FLUX.1 backbone (``FluxTransformer2DModel``) in pure JAX.

The block equations follow Black Forest Labs' reference code
(``src/flux/model.py`` and ``src/flux/modules/layers.py``):

* embedding: ``vec = time_in(temb(1000 t)) + guidance_in(temb(1000 g)) +
  vector_in(pooled)``, each ``*_in`` Linear -> SiLU -> Linear; image
  tokens ``img_in(patchify(latents))``, text tokens ``txt_in(emb)``;
* ``n_double`` two-stream blocks: per stream a modulation of 6 (shift,
  scale, gate twice) from ``silu(vec)``, LayerNorm without affine, q, k, v
  with bias, QK RMSNorm per head, joint attention over [text; image] with
  3-axis RoPE on q and k, then ``x += gate * proj(attn)`` and
  ``x += gate * mlp(...)``;
* ``n_single`` single-stream blocks over ``[text; image]``: a modulation
  of 3, one fused ``linear1`` to ``[q k v | mlp]``, attention and the
  GELU MLP in parallel, and ``x += gate * linear2([attn | gelu(mlp)])``;
* a final adaLN (shift, scale), a linear head and unpatchify.

Gates are plain ``gate`` as published (the MMDiT block here uses
``1 + gate``).  The pooled CLIP vector is a stand-in computed inside the
forward: the mean over the text sequence of the first ``pooled_dim``
channels of the text embedding.  Each of the two block stacks runs as one
``lax.scan``, under the named scopes ``double_stream`` and
``single_stream``.

Weights are random from a seed: every linear draws its weight at
``1/sqrt(d_in)`` and its bias at ``BIAS_SCALE`` (so gates are of order
one and every block moves the stream), QK norm scales are one.  The
double blocks keep the MMDiT stream's key names (``layers/img/wq`` ...),
so LoRA adapters and quantization address them the same way.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.diffusion.config import FluxConfig
from repro.diffusion.mmdit import (
    _lora_proj,
    patchify,
    quantize_mmdit_params,
    unpatchify,
)
from repro.nn.layers import (
    dense_init,
    gqa_attention,
    qdense,
    quantize_dense,
    split,
    timestep_embedding,
)

Params = Dict[str, Any]
F32 = jnp.float32
BIAS_SCALE = 0.1
EPS = 1e-6


# ------------------------------------------------------------------ weights

def _linear(key: jax.Array, d_in: int, d_out: int, dtype: Any
            ) -> Tuple[jax.Array, jax.Array]:
    kw, kb = jax.random.split(key)
    b = (jax.random.normal(kb, (d_out,), F32) * BIAS_SCALE).astype(dtype)
    return dense_init(kw, d_in, d_out, dtype), b


def _init_stream(key: jax.Array, cfg: FluxConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = split(key, 7)
    p: Params = {}
    for k, (w, b), din, dout in (
            (ks[0], ("ada", "ada_b"), d, 6 * d), (ks[1], ("wq", "bq"), d, d),
            (ks[2], ("wk", "bk"), d, d), (ks[3], ("wv", "bv"), d, d),
            (ks[4], ("wo", "bo"), d, d), (ks[5], ("w1", "b1"), d, cfg.d_ff),
            (ks[6], ("w2", "b2"), cfg.d_ff, d)):
        p[w], p[b] = _linear(k, din, dout, cfg.dtype)
    p["q_norm"] = jnp.ones((hd,), cfg.dtype)
    p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def _init_double(key: jax.Array, cfg: FluxConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {"img": _init_stream(k1, cfg), "txt": _init_stream(k2, cfg)}


def _init_single(key: jax.Array, cfg: FluxConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = split(key, 3)
    p: Params = {}
    p["ada"], p["ada_b"] = _linear(ks[0], d, 3 * d, cfg.dtype)
    p["w1"], p["b1"] = _linear(ks[1], d, 3 * d + cfg.d_ff, cfg.dtype)
    p["w2"], p["b2"] = _linear(ks[2], d + cfg.d_ff, d, cfg.dtype)
    p["q_norm"] = jnp.ones((hd,), cfg.dtype)
    p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def _init_embedder(key: jax.Array, d_in: int, cfg: FluxConfig) -> Params:
    k1, k2 = jax.random.split(key)
    w1, b1 = _linear(k1, d_in, cfg.d_model, cfg.dtype)
    w2, b2 = _linear(k2, cfg.d_model, cfg.d_model, cfg.dtype)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


@partial(jax.jit, static_argnums=(1,))
def init_flux(key: jax.Array, cfg: FluxConfig) -> Params:
    """Every weight in one program, each leaf drawn in f32 and rounded to
    the config dtype inside it."""
    d = cfg.d_model
    ks = split(key, 10)
    params: Params = {
        "layers": jax.vmap(lambda k: _init_double(k, cfg))(
            jax.random.split(ks[0], cfg.n_double)),
        "single": jax.vmap(lambda k: _init_single(k, cfg))(
            jax.random.split(ks[1], cfg.n_single)),
        "time_in": _init_embedder(ks[2], 256, cfg),
        "vector_in": _init_embedder(ks[3], cfg.pooled_dim, cfg),
    }
    params["img_in"], params["img_in_b"] = _linear(ks[4], cfg.in_dim, d,
                                                   cfg.dtype)
    params["txt_in"], params["txt_in_b"] = _linear(ks[5], cfg.text_dim, d,
                                                   cfg.dtype)
    params["final_ada"], params["final_ada_b"] = _linear(ks[6], d, 2 * d,
                                                         cfg.dtype)
    params["final_proj"], params["final_proj_b"] = _linear(
        ks[7], d, cfg.in_dim, cfg.dtype)
    if cfg.guidance_embed:
        params["guidance_in"] = _init_embedder(ks[8], 256, cfg)
    return params


_QUANT_SINGLE_KEYS = ("ada", "w1", "w2")


def quantize_flux_params(params: Params) -> Params:
    """Both block stacks' projections quantized per the active
    ``REPRO_QUANT`` mode (identity when off), as for the MMDiT."""
    out = quantize_mmdit_params(params)
    out["single"] = {k: (quantize_dense(v) if k in _QUANT_SINGLE_KEYS else v)
                     for k, v in params["single"].items()}
    return out


# ------------------------------------------------------------------ pieces

def rope_angles(cfg: FluxConfig) -> jax.Array:
    """Rotation angles [tokens, head_dim / 2] of the joint [text; image]
    sequence: text tokens sit at position (0, 0, 0), image token (r, c)
    of the patch grid at (0, r, c); axis ``a`` takes ``rope_axes[a]``
    channels, pair ``j`` of them at frequency ``theta ** (-2j / dim)``."""
    g = cfg.latent_size // cfg.patch
    rows, cols = jnp.meshgrid(jnp.arange(g), jnp.arange(g), indexing="ij")
    img = jnp.stack([jnp.zeros(g * g), rows.ravel(), cols.ravel()], -1)
    ids = jnp.concatenate([jnp.zeros((cfg.text_tokens, 3)), img]).astype(F32)
    parts = []
    for a, dim in enumerate(cfg.rope_axes):
        omega = cfg.rope_theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
        parts.append(ids[:, a:a + 1] * omega[None])
    return jnp.concatenate(parts, axis=-1)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate adjacent channel pairs of ``x`` [B, T, H, hd] (f32) by
    ``angles`` [T, hd / 2]."""
    cos = jnp.cos(angles)[None, :, None]
    sin = jnp.sin(angles)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([cos * x0 - sin * x1, sin * x0 + cos * x1], axis=-1)
    return out.reshape(x.shape)


def _layer_norm(x: jax.Array) -> jax.Array:
    """LayerNorm without affine, in f32."""
    x = x.astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _modulated(x: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    return (_layer_norm(x) * (1 + scale[:, None]) + shift[:, None]).astype(
        x.dtype)


def _mods(vec: jax.Array, w: Any, b: jax.Array, n: int):
    return jnp.split(qdense(jax.nn.silu(vec), w) + b, n, axis=-1)


def _qk_norm(p: Params, q: jax.Array, k: jax.Array):
    """QK RMSNorm per head, in f32 with the learned scales; [B, T, H, hd]."""
    def norm(x, w):
        x = x.astype(F32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
        return x * w.astype(F32)

    return norm(q, p["q_norm"]), norm(k, p["k_norm"])


def _heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, d = x.shape
    return x.reshape(b, s, n_heads, d // n_heads)


def _embed(p: Params, x: jax.Array) -> jax.Array:
    """MLPEmbedder: Linear -> SiLU -> Linear, f32 out."""
    return (jax.nn.silu(x @ p["w1"] + p["b1"]) @ p["w2"]) + p["b2"]


# ------------------------------------------------------------------ blocks

def _stream_in(p: Params, x: jax.Array, vec: jax.Array, n_heads: int, lora):
    s1, c1, g1, s2, c2, g2 = _mods(vec, p["ada"], p["ada_b"], 6)
    h = _modulated(x, s1, c1)
    q = _lora_proj(h, p["wq"], lora, "wq") + p["bq"]
    k = _lora_proj(h, p["wk"], lora, "wk") + p["bk"]
    v = _lora_proj(h, p["wv"], lora, "wv") + p["bv"]
    return (_heads(q, n_heads), _heads(k, n_heads), _heads(v, n_heads),
            (g1, s2, c2, g2))


def _stream_out(p: Params, x: jax.Array, attn: jax.Array, mods, lora):
    g1, s2, c2, g2 = mods
    b, s = attn.shape[:2]
    proj = _lora_proj(attn.reshape(b, s, -1), p["wo"], lora, "wo") + p["bo"]
    x = x + (g1[:, None] * proj).astype(x.dtype)
    m = jax.nn.gelu(qdense(_modulated(x, s2, c2), p["w1"]) + p["b1"],
                    approximate=True)
    return x + (g2[:, None] * (qdense(m, p["w2"]) + p["b2"])).astype(x.dtype)


def double_block(p: Params, img: jax.Array, txt: jax.Array, vec: jax.Array,
                 angles: jax.Array, n_heads: int, lora=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Two-stream block; LoRA (when given) on the image stream's q, k, v
    and out projections."""
    qi, ki, vi, mods_i = _stream_in(p["img"], img, vec, n_heads, lora)
    qt, kt, vt, mods_t = _stream_in(p["txt"], txt, vec, n_heads, None)
    qi, ki = _qk_norm(p["img"], qi, ki)
    qt, kt = _qk_norm(p["txt"], qt, kt)
    dt = img.dtype
    q = apply_rope(jnp.concatenate([qt, qi], 1), angles).astype(dt)
    k = apply_rope(jnp.concatenate([kt, ki], 1), angles).astype(dt)
    out = gqa_attention(q, k, jnp.concatenate([vt, vi], 1), causal=False)
    tc = txt.shape[1]
    img = _stream_out(p["img"], img, out[:, tc:], mods_i, lora)
    txt = _stream_out(p["txt"], txt, out[:, :tc], mods_t, None)
    return img, txt


def single_block(p: Params, x: jax.Array, vec: jax.Array, angles: jax.Array,
                 n_heads: int) -> jax.Array:
    """Single-stream parallel block over the joint [text; image] tokens."""
    shift, scale, gate = _mods(vec, p["ada"], p["ada_b"], 3)
    d = x.shape[-1]
    y = qdense(_modulated(x, shift, scale), p["w1"]) + p["b1"]
    q, k, v = (_heads(y[..., i * d:(i + 1) * d], n_heads) for i in range(3))
    q, k = _qk_norm(p, q, k)
    attn = gqa_attention(apply_rope(q, angles).astype(x.dtype),
                         apply_rope(k, angles).astype(x.dtype), v,
                         causal=False)
    b, s = x.shape[:2]
    mlp = jax.nn.gelu(y[..., 3 * d:], approximate=True)
    out = qdense(jnp.concatenate([attn.reshape(b, s, d), mlp], -1),
                 p["w2"]) + p["b2"]
    return x + (gate[:, None] * out).astype(x.dtype)


# ---------------------------------------------------------------- backbone

def flux_apply(
    params: Params,
    cfg: FluxConfig,
    latents: jax.Array,                       # [B, S, S, C]
    t: jax.Array,                             # [B]
    text_emb: jax.Array,                      # [B, Tc, text_dim]
    guidance: Optional[jax.Array] = None,     # [B]; read when embedded
    lora_stack: Optional[Params] = None,      # stack_loras output ([L,G,...])
    lora_idx: Optional[jax.Array] = None,     # [B] int32; -1 = no adapter
) -> jax.Array:
    """One denoising forward pass of B rows; returns the velocity [B,S,S,C]
    (f32).  Guidance enters as an input of the backbone: one row per
    request-step, no unconditional row."""
    dt = cfg.dtype
    img = patchify(latents, cfg.patch).astype(dt) @ params["img_in"] \
        + params["img_in_b"]
    txt = text_emb.astype(dt) @ params["txt_in"] + params["txt_in_b"]
    vec = _embed(params["time_in"], timestep_embedding(t, 256))
    if cfg.guidance_embed:
        vec = vec + _embed(params["guidance_in"],
                           timestep_embedding(guidance, 256))
    pooled = jnp.mean(text_emb[..., :cfg.pooled_dim].astype(F32), axis=1)
    vec = vec + _embed(params["vector_in"], pooled)
    angles = rope_angles(cfg)

    if lora_stack is None:
        lora_xs = None
    else:
        scales = lora_stack["scales"]
        idx = lora_idx.astype(jnp.int32)
        lora_xs = {k: v for k, v in lora_stack.items() if k != "scales"}

    def double(carry, xs):
        layer_p, layer_lora = xs
        lora = None if layer_lora is None else (layer_lora, idx, scales)
        return double_block(layer_p, *carry, vec, angles, cfg.n_heads,
                            lora=lora), None

    def single(x, layer_p):
        return single_block(layer_p, x, vec, angles, cfg.n_heads), None

    with jax.named_scope("double_stream"):
        (img, txt), _ = jax.lax.scan(double, (img, txt),
                                     (params["layers"], lora_xs))
    with jax.named_scope("single_stream"):
        x, _ = jax.lax.scan(single, jnp.concatenate([txt, img], 1),
                            params["single"])
    shift, scale = _mods(vec, params["final_ada"], params["final_ada_b"], 2)
    img = _layer_norm(x[:, txt.shape[1]:]) * (1 + scale[:, None]) \
        + shift[:, None]
    out = img @ params["final_proj"] + params["final_proj_b"]
    return unpatchify(out, cfg.patch, cfg.latent_size, cfg.latent_channels)
