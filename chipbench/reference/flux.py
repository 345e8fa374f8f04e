"""FLUX.1 backbone (``FluxTransformer2DModel``), embedded guidance and the
shifted flow-matching Euler step, in float32 at highest precision, layer by
layer.  Black Forest Labs' reference code (``src/flux/model.py``,
``src/flux/modules/layers.py``) gives the equations:

    vec = time_in(temb(1000 t)) + guidance_in(temb(1000 g)) + vector_in(y)
          (each *_in: Linear -> SiLU -> Linear; temb the 256-wide sinusoid,
          cos first)
    img = img_in(patchify(latents)),  txt = txt_in(emb)
    pe  = RoPE over (axis 0, row, col) ids: text (0, 0, 0), image (0, r, c);
          axis a takes axes_dims_rope[a] channels, pair j at theta^(-2j/dim)

  double-stream block, for each of img and txt:
    shift1, scale1, gate1, shift2, scale2, gate2 = Linear(silu(vec))
    h = (1 + scale1) LN(x) + shift1        (LN: no affine, eps 1e-6)
    q, k, v = Linear(h);  q, k = RMSNorm(q) * s_q, RMSNorm(k) * s_k per head
    attn = softmax(rope(q) rope(k)^T / sqrt(hd)) v over [txt; img]
    x += gate1 * proj(attn)
    x += gate2 * W2 gelu_tanh(W1 ((1 + scale2) LN(x) + shift2))

  single-stream block, on x = [txt; img]:
    shift, scale, gate = Linear(silu(vec))
    [q k v | m] = linear1((1 + scale) LN(x) + shift)      (3d and d_ff wide)
    x += gate * linear2([attn(q, k, v) | gelu_tanh(m)])

  head: shift, scale = Linear(silu(vec));
        velocity = unpatchify(Linear((1 + scale) LN(img) + shift))

and a step moves the latents by (t_next - t_cur) * velocity along
linspace(1, 0, steps + 1) shifted to s t / (1 + (s - 1) t), s = exp(mu),
mu linear in the image token count (base_shift at base_image_seq_len,
max_shift at max_image_seq_len); unshifted where the configuration's
scheduler does not shift.  Guidance is distilled into the backbone: one
row per request-step, no unconditional row.

Departures from BFL's model, which the program shares and this reference
therefore follows:

* the pooled CLIP-L vector y is a stand-in: the mean over the text
  sequence of the first ``pooled_projection_dim`` channels of the prompt
  embedding (no CLIP-L runs);
* the prompt embedding comes from the repository's stand-in text encoder
  (not T5-XXL), and the image from its stand-in VAE;
* a patch token lists its channels as (row, column, channel) of the 2x2
  patch, not BFL's (channel, row, column): a fixed permutation of
  ``img_in``'s input and ``final_proj``'s output, which random weights do
  not see;
* weights are random: every linear's weight at 1/sqrt(d_in), its bias at
  ``BIAS_SCALE``, QK norm scales one.

Weights are rebuilt from ``PRNGKey(crc32(model_id) % 2**31)`` with the key
tree and scales of the program's initializer, rounded to the served dtype
(bfloat16), and then used in float32.  One block's weights exist at a
time (the largest, a double block at published width, is 1.36 GB in
float32).

The module meets the architecture contract of ``chipbench/reference``: it
also counts the backbone's operations and bytes from its shapes, beside
the equations they count.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.mmdit import (
    F32,
    initial_latents,  # noqa: F401  (the contract's, shared with mmdit)
    matmul,
    model_key,
    patchify,
    timestep_embedding,
    unpatchify,
)

BIAS_SCALE = 0.1
EPS = 1e-6
# attention is computed this many heads at a time: the f32 logits of one
# head at 4608 tokens take 85 MB
HEAD_CHUNK = 4


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Sizes of one served FLUX workflow, as a configuration file states
    them (see ``geometry_from_config``)."""

    family: str
    d_model: int
    n_double: int
    n_single: int
    n_heads: int
    d_ff: int
    text_dim: int
    pooled_dim: int
    latent_size: int
    latent_channels: int
    patch: int
    text_tokens: int
    rope_axes: Tuple[int, ...]
    rope_theta: float
    guidance_embed: bool
    # the scheduler's shift: None for none, else (base_shift, max_shift,
    # base_image_seq_len, max_image_seq_len)
    shift: Optional[Tuple[float, float, int, int]]
    dtype: str                 # served weight dtype of the backbone
    te_vocab: int
    te_layers: int
    te_heads: int
    te_dtype: str
    vae_base: int
    vae_dtype: str

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def image_tokens(self) -> int:
        return (self.latent_size // self.patch) ** 2

    @property
    def tokens(self) -> int:
        return self.image_tokens + self.text_tokens

    @property
    def in_dim(self) -> int:
        return self.patch * self.patch * self.latent_channels


def geometry_from_config(cfg: Dict[str, Any]) -> Geometry:
    """The sizes of a configuration file: diffusers ``transformer`` keys
    (whose ``in_channels`` are the pipeline's packed 2x2 patches of the
    VAE's channels), the pipeline's packing and latent size, its
    ``scheduler`` keys, and the stand-in encoder and VAE."""
    pack = cfg["pack_size"] * cfg["patch_size"]
    te, vae, sch = cfg["text_encoder"], cfg["vae"], cfg["scheduler"]
    d = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    shift = ((sch["base_shift"], sch["max_shift"], sch["base_image_seq_len"],
              sch["max_image_seq_len"])
             if sch["use_dynamic_shifting"] else None)
    return Geometry(
        family=cfg["family"], d_model=d, n_double=cfg["num_layers"],
        n_single=cfg["num_single_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=int(cfg["mlp_ratio"] * d),
        text_dim=cfg["joint_attention_dim"],
        pooled_dim=cfg["pooled_projection_dim"],
        latent_size=cfg["latent_size"],
        latent_channels=cfg["in_channels"] // (pack * pack), patch=pack,
        text_tokens=cfg["text_tokens"],
        rope_axes=tuple(cfg["axes_dims_rope"]),
        rope_theta=float(cfg["rope_theta"]),
        guidance_embed=bool(cfg["guidance_embeds"]), shift=shift,
        dtype=cfg["dtype"], te_vocab=te["vocab"], te_layers=te["layers"],
        te_heads=te["heads"], te_dtype=te["dtype"], vae_base=vae["base"],
        vae_dtype=vae["dtype"])


# ------------------------------------------------------------------ weights

def _linear(key, d_in: int, d_out: int, dtype: str):
    kw, kb = jax.random.split(key)
    b = jax.random.normal(kb, (d_out,), F32) * BIAS_SCALE
    w = jax.random.normal(kw, (d_in, d_out), F32) / math.sqrt(d_in)
    return w.astype(dtype).astype(F32), b.astype(dtype).astype(F32)


def _stream_weights(key, g: Geometry) -> Dict[str, jax.Array]:
    d = g.d_model
    ks = list(jax.random.split(key, 7))
    p = {}
    for k, (w, b), din, dout in (
            (ks[0], ("ada", "ada_b"), d, 6 * d), (ks[1], ("wq", "bq"), d, d),
            (ks[2], ("wk", "bk"), d, d), (ks[3], ("wv", "bv"), d, d),
            (ks[4], ("wo", "bo"), d, d), (ks[5], ("w1", "b1"), d, g.d_ff),
            (ks[6], ("w2", "b2"), g.d_ff, d)):
        p[w], p[b] = _linear(k, din, dout, g.dtype)
    p["q_norm"] = p["k_norm"] = jnp.ones((g.head_dim,), F32)
    return p


@partial(jax.jit, static_argnums=(1,))
def double_weights(block_key, g: Geometry) -> Dict[str, Dict[str, jax.Array]]:
    k1, k2 = jax.random.split(block_key)
    return {"img": _stream_weights(k1, g), "txt": _stream_weights(k2, g)}


@partial(jax.jit, static_argnums=(1,))
def single_weights(block_key, g: Geometry) -> Dict[str, jax.Array]:
    d = g.d_model
    ks = list(jax.random.split(block_key, 3))
    p = {}
    p["ada"], p["ada_b"] = _linear(ks[0], d, 3 * d, g.dtype)
    p["w1"], p["b1"] = _linear(ks[1], d, 3 * d + g.d_ff, g.dtype)
    p["w2"], p["b2"] = _linear(ks[2], d + g.d_ff, d, g.dtype)
    p["q_norm"] = p["k_norm"] = jnp.ones((g.head_dim,), F32)
    return p


def _embedder(key, d_in: int, g: Geometry) -> Dict[str, jax.Array]:
    k1, k2 = jax.random.split(key)
    w1, b1 = _linear(k1, d_in, g.d_model, g.dtype)
    w2, b2 = _linear(k2, g.d_model, g.d_model, g.dtype)
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


@partial(jax.jit, static_argnums=(1,))
def outer_weights(key, g: Geometry) -> Dict[str, Any]:
    """Embedders and the head; ``double`` and ``single`` hold the
    per-block keys."""
    ks = list(jax.random.split(key, 10))
    d = g.d_model
    o = {"double": jax.random.split(ks[0], g.n_double),
         "single": jax.random.split(ks[1], g.n_single),
         "time_in": _embedder(ks[2], 256, g),
         "vector_in": _embedder(ks[3], g.pooled_dim, g)}
    o["img_in"] = _linear(ks[4], g.in_dim, d, g.dtype)
    o["txt_in"] = _linear(ks[5], g.text_dim, d, g.dtype)
    o["final_ada"] = _linear(ks[6], d, 2 * d, g.dtype)
    o["final_proj"] = _linear(ks[7], d, g.in_dim, g.dtype)
    if g.guidance_embed:
        o["guidance_in"] = _embedder(ks[8], 256, g)
    return o


# ------------------------------------------------------------------ forward

def layer_norm(x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True) + EPS)


def rms_norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def rope(g: Geometry) -> jax.Array:
    """BFL's ``EmbedND``: per token and channel pair the 2x2 rotation
    [[cos, -sin], [sin, cos]], [tokens, head_dim / 2, 2, 2]."""
    side = g.latent_size // g.patch
    ids = np.zeros((g.tokens, 3))
    r, c = np.divmod(np.arange(side * side), side)
    ids[g.text_tokens:, 1], ids[g.text_tokens:, 2] = r, c
    mats = []
    for a, dim in enumerate(g.rope_axes):
        omega = 1.0 / g.rope_theta ** (np.arange(0, dim, 2) / dim)
        ang = ids[:, a, None] * omega[None]
        cos, sin = np.cos(ang), np.sin(ang)
        mats.append(np.stack([cos, -sin, sin, cos], -1).reshape(
            *ang.shape, 2, 2))
    return jnp.asarray(np.concatenate(mats, axis=1), F32)


def apply_rope(x: jax.Array, pe: jax.Array) -> jax.Array:
    """BFL's ``apply_rope`` on [B, T, H, hd]: adjacent pairs rotated."""
    b, t, h, hd = x.shape
    xp = x.reshape(b, t, h, hd // 2, 1, 2)
    f = pe[None, :, None]                           # [1, T, 1, hd/2, 2, 2]
    return (f[..., 0] * xp[..., 0] + f[..., 1] * xp[..., 1]).reshape(x.shape)


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """softmax(q k^T / sqrt(hd)) v over [B, T, H, hd], a few heads at a
    time so the logits of all heads never coexist."""
    b, t, h, hd = q.shape
    c = HEAD_CHUNK if h % HEAD_CHUNK == 0 else 1
    split = lambda a: a.reshape(b, t, h // c, c, hd).transpose(2, 0, 1, 3, 4)

    def one(args):
        qc, kc, vc = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc) / math.sqrt(hd)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vc)

    out = jax.lax.map(one, (split(q), split(k), split(v)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, t, h, hd)


def _lin(x, wb, fp8=False):
    w, b = wb
    return matmul(x, w, fp8) + b


def _stream_in(p, x, vec, n_heads, fp8):
    s1, c1, g1, s2, c2, g2 = jnp.split(
        matmul(jax.nn.silu(vec), p["ada"], fp8) + p["ada_b"], 6, -1)
    h = layer_norm(x) * (1 + c1[:, None]) + s1[:, None]
    b, s, d = h.shape
    heads = lambda w, bias: (matmul(h, p[w], fp8) + p[bias]).reshape(
        b, s, n_heads, d // n_heads)
    q = rms_norm(heads("wq", "bq")) * p["q_norm"]
    k = rms_norm(heads("wk", "bk")) * p["k_norm"]
    return q, k, heads("wv", "bv"), (g1, s2, c2, g2)


def _stream_out(p, x, attn, mods, fp8):
    g1, s2, c2, g2 = mods
    b, s = attn.shape[:2]
    x = x + g1[:, None] * (matmul(attn.reshape(b, s, -1), p["wo"], fp8)
                           + p["bo"])
    h = layer_norm(x) * (1 + c2[:, None]) + s2[:, None]
    m = jax.nn.gelu(matmul(h, p["w1"], fp8) + p["b1"], approximate=True)
    return x + g2[:, None] * (matmul(m, p["w2"], fp8) + p["b2"])


@partial(jax.jit, static_argnums=(5, 6))
def double_block(w, img, txt, vec, pe, n_heads: int, fp8: bool = False):
    qi, ki, vi, mi = _stream_in(w["img"], img, vec, n_heads, fp8)
    qt, kt, vt, mt = _stream_in(w["txt"], txt, vec, n_heads, fp8)
    cat = lambda a, b: jnp.concatenate([a, b], 1)
    out = attention(apply_rope(cat(qt, qi), pe), apply_rope(cat(kt, ki), pe),
                    cat(vt, vi))
    tc = txt.shape[1]
    return (_stream_out(w["img"], img, out[:, tc:], mi, fp8),
            _stream_out(w["txt"], txt, out[:, :tc], mt, fp8))


@partial(jax.jit, static_argnums=(4, 5))
def single_block(p, x, vec, pe, n_heads: int, fp8: bool = False):
    shift, scale, gate = jnp.split(
        matmul(jax.nn.silu(vec), p["ada"], fp8) + p["ada_b"], 3, -1)
    h = layer_norm(x) * (1 + scale[:, None]) + shift[:, None]
    b, s, d = x.shape
    y = matmul(h, p["w1"], fp8) + p["b1"]
    q, k, v = (y[..., i * d:(i + 1) * d].reshape(b, s, n_heads, d // n_heads)
               for i in range(3))
    q, k = rms_norm(q) * p["q_norm"], rms_norm(k) * p["k_norm"]
    attn = attention(apply_rope(q, pe), apply_rope(k, pe), v)
    mlp = jax.nn.gelu(y[..., 3 * d:], approximate=True)
    out = matmul(jnp.concatenate([attn.reshape(b, s, d), mlp], -1), p["w2"],
                 fp8) + p["b2"]
    return x + gate[:, None] * out


def _mlp_embed(p, x):
    return jax.nn.silu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


@partial(jax.jit, static_argnums=(5,))
def _embed(o, lat, t, guidance, emb, g: Geometry):
    img = _lin(patchify(lat, g.patch), o["img_in"])
    txt = _lin(emb, o["txt_in"])
    vec = _mlp_embed(o["time_in"], timestep_embedding(t))
    if g.guidance_embed:
        vec = vec + _mlp_embed(o["guidance_in"], timestep_embedding(guidance))
    pooled = jnp.mean(emb[..., :g.pooled_dim], axis=1)
    return img, txt, vec + _mlp_embed(o["vector_in"], pooled)


@partial(jax.jit, static_argnums=(3,))
def _head(o, img, vec, g: Geometry):
    shift, scale = jnp.split(_lin(jax.nn.silu(vec), o["final_ada"]), 2, -1)
    img = layer_norm(img) * (1 + scale[:, None]) + shift[:, None]
    return unpatchify(_lin(img, o["final_proj"]), g.patch, g.latent_size,
                      g.latent_channels)


def velocity(g: Geometry, lat: jax.Array, t: jax.Array, emb: jax.Array,
             guidance: jax.Array, fp8: bool = False) -> jax.Array:
    """Backbone prediction for rows ``lat`` [B,S,S,C] at times ``t`` [B]
    with guidance ``guidance`` [B] under text embeddings ``emb``
    [B,Tc,text_dim]; float32, with the blocks' projections computed in
    float8 when ``fp8`` (the control)."""
    with jax.default_matmul_precision("highest"):
        o = outer_weights(model_key(f"backbone:{g.family}"), g)
        img, txt, vec = _embed(o, lat.astype(F32), t.astype(F32),
                               guidance.astype(F32), emb.astype(F32), g)
        pe = rope(g)
        for bk in o["double"]:
            img, txt = double_block(double_weights(bk, g), img, txt, vec, pe,
                                    g.n_heads, fp8)
        x = jnp.concatenate([txt, img], 1)
        for bk in o["single"]:
            x = single_block(single_weights(bk, g), x, vec, pe, g.n_heads, fp8)
        return _head(o, x[:, g.text_tokens:], vec, g)


def flow_schedule(g: Geometry, steps: int) -> np.ndarray:
    """t_0 = 1 ... t_steps = 0, shifted as the configuration's scheduler
    says, in float32."""
    t = np.linspace(1.0, 0.0, steps + 1)
    if g.shift is not None:
        base, top, x1, x2 = g.shift
        mu = base + (top - base) / (x2 - x1) * (g.image_tokens - x1)
        s = math.exp(mu)
        t = s * t / (1 + (s - 1) * t)
    return t.astype(np.float32)


def sample(g: Geometry, lat: jax.Array, emb: jax.Array, steps: int,
           guidance: float, start: int = 0, stop: Optional[int] = None,
           fp8: bool = False) -> jax.Array:
    """Steps ``start`` .. ``stop`` of a ``steps``-step schedule of one
    request, guidance fed to the backbone (one row per step)."""
    sched = flow_schedule(g, steps)
    lat = jnp.asarray(lat, F32)
    gv = jnp.full((lat.shape[0],), guidance, F32)
    for i in range(start, steps if stop is None else stop):
        t_cur, t_next = float(sched[i]), float(sched[i + 1])
        v = velocity(g, lat, jnp.full((lat.shape[0],), t_cur, F32), emb, gv,
                     fp8)
        lat = lat + (t_next - t_cur) * v
    return lat


# ------------------------------------------------------------------ counts
# counted as ``chipbench/flops.py`` says: a multiply-add is 2 FLOPs, and
# elementwise work (norms, RoPE, modulation, softmax) is left out

def rows_per_step(g: Geometry) -> int:
    """Guidance is distilled into the backbone: one row."""
    return 1


def attention_flops(g: Geometry) -> float:
    """QK^T and PV of one joint attention call for one row, unpadded."""
    return 4.0 * g.tokens * g.tokens * g.d_model


def attention_bytes(g: Geometry, itemsize: int = 2) -> float:
    """Least HBM traffic of that call: q, k, v read once, o written once."""
    return 4.0 * g.tokens * g.d_model * itemsize


def double_flops(g: Geometry) -> float:
    """One double block for one row: q, k, v, o and the two MLP matmuls
    of each stream over its tokens, both modulations, the attention."""
    d = g.d_model
    per_token = 2 * (4 * d * d + 2 * d * g.d_ff)
    return per_token * g.tokens + 2 * 2 * d * 6 * d + attention_flops(g)


def single_flops(g: Geometry) -> float:
    """One single block for one row: linear1 (d -> 3d + d_ff), linear2
    (d + d_ff -> d) over every token, the modulation, the attention."""
    d = g.d_model
    per_token = 2 * d * (3 * d + g.d_ff) + 2 * (d + g.d_ff) * d
    return per_token * g.tokens + 2 * d * 3 * d + attention_flops(g)


def row_step_flops(g: Geometry) -> float:
    """One backbone forward of one row: every block, the image, text,
    time, guidance and pooled-vector embeddings and the final adaLN and
    head."""
    d = g.d_model
    mlp = lambda d_in: 2 * (d_in * d + d * d)
    embed = (2 * (g.image_tokens * g.in_dim * d + g.text_tokens * g.text_dim
                  * d) + mlp(256) + mlp(g.pooled_dim)
             + (mlp(256) if g.guidance_embed else 0))
    head = 2 * (d * 2 * d + g.image_tokens * d * g.in_dim)
    return (g.n_double * double_flops(g) + g.n_single * single_flops(g)
            + embed + head)


def attention_calls(g: Geometry) -> List[Tuple[float, float]]:
    """One joint attention call per block, double and single alike."""
    return [(attention_flops(g), attention_bytes(g))] * (g.n_double
                                                         + g.n_single)


# ------------------------------------------------- the program's config

REDUCIBLE = {"num_layers": "n_double", "num_single_layers": "n_single"}


def program_fields(g: Geometry) -> Dict[str, Any]:
    """The program's ``FluxConfig`` at these sizes."""
    return dict(d_model=g.d_model, n_double=g.n_double, n_single=g.n_single,
                n_heads=g.n_heads, d_ff=g.d_ff, text_dim=g.text_dim,
                pooled_dim=g.pooled_dim, latent_size=g.latent_size,
                latent_channels=g.latent_channels, patch=g.patch,
                text_tokens=g.text_tokens, rope_axes=g.rope_axes,
                rope_theta=g.rope_theta, guidance_embed=g.guidance_embed,
                time_shift=g.shift is not None, dtype=getattr(jnp, g.dtype))
