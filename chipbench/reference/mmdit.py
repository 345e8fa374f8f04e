"""MMDiT backbone, classifier-free guidance and the rectified-flow Euler
step, in float32 at highest precision, layer by layer.

Equations (per block, for each of the image and text streams):

    ada = silu(t_emb) @ W_ada + b_ada  ->  shift1, scale1, gate1, shift2, scale2, gate2
    h   = rmsnorm(x) * (1 + scale1) + shift1
    q, k, v = h @ Wq, h @ Wk, h @ Wv            (heads of 64)
    joint attention over [text; image] tokens, softmax(q k^T / 8) v
    x  += (1 + gate1) * (attn @ Wo)
    h   = rmsnorm(x) * (1 + scale2) + shift2
    x  += (1 + gate2) * (gelu_tanh(h @ W1) @ W2)

then a final adaLN (shift, scale), a linear head and unpatchify.  The
velocity with guidance g is v_u + g (v_c - v_u), where the unconditional
row sees an all-zero text embedding, and a step moves the latents by
(t_next - t_cur) * v.

Departures from the published SD3 / SD3.5 MMDiT, which the program shares
and this reference therefore follows: no pooled-text projection into the
timestep embedding, no positional embedding of the image patches, a full
(not context-pre-only) last text block, and no QK RMSNorm (SD3.5 has it).

Weights are rebuilt from ``PRNGKey(crc32(model_id) % 2**31)`` with the key
tree and scales of the program's initializer, rounded to the served dtype
(bfloat16), and then used in float32.  One layer's weights exist at a time,
so the reference fits beside nothing else on one chip at every width.

The module meets the architecture contract of ``chipbench/reference``: it
also counts the backbone's operations and bytes from its shapes, beside
the equations they count.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# attention is computed this many heads at a time: the f32 logits of one
# head at 4429 tokens and two CFG rows take 157 MB
HEAD_CHUNK = 2


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Sizes of one served MMDiT workflow, as a configuration file states
    them (see ``geometry_from_config``)."""

    family: str
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    text_dim: int
    latent_size: int
    latent_channels: int
    patch: int
    text_tokens: int
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
    """The sizes of a configuration file (diffusers ``transformer``
    config keys, plus the stand-in encoder and VAE)."""
    d = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    te, vae = cfg["text_encoder"], cfg["vae"]
    return Geometry(
        family=cfg["family"], d_model=d, n_layers=cfg["num_layers"],
        n_heads=cfg["num_attention_heads"], d_ff=cfg["mlp_ratio"] * d,
        text_dim=cfg["joint_attention_dim"], latent_size=cfg["sample_size"],
        latent_channels=cfg["in_channels"], patch=cfg["patch_size"],
        text_tokens=cfg["text_tokens"], dtype=cfg["dtype"],
        te_vocab=te["vocab"], te_layers=te["layers"], te_heads=te["heads"],
        te_dtype=te["dtype"], vae_base=vae["base"], vae_dtype=vae["dtype"])


def model_key(model_id: str) -> jax.Array:
    """The program's weight seed for a model id."""
    return jax.random.PRNGKey(zlib.crc32(model_id.encode("utf-8")) % 2**31)


def _split(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def _dense(key, d_in: int, d_out: int, dtype: str, scale=None) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    w = jax.random.normal(key, (d_in, d_out), dtype=F32) * scale
    return w.astype(dtype).astype(F32)


FP8_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def fp8_weights(w: jax.Array) -> jax.Array:
    """``w`` [..., K, N] rounded to float8 e4m3, one scale per output
    channel."""
    return _fp8(w, -2)


def matmul(x: jax.Array, w: jax.Array, fp8: bool = False) -> jax.Array:
    """``x @ w``; with ``fp8`` both operands rounded to float8 e4m3 first
    (one scale per row of ``x``, per column of ``w``): a projection of the
    reference computed in the precision below bfloat16, the control that
    the limits must fail."""
    if fp8:
        x, w = _fp8(x, -1), fp8_weights(w)
    return x @ w


# ------------------------------------------------------------------ weights

def _stream_weights(key, g: Geometry) -> Dict[str, jax.Array]:
    d = g.d_model
    ks = _split(key, 8)
    return {
        "ada": _dense(ks[0], d, 6 * d, g.dtype, scale=0.02),
        "wq": _dense(ks[1], d, d, g.dtype),
        "wk": _dense(ks[2], d, d, g.dtype),
        "wv": _dense(ks[3], d, d, g.dtype),
        "wo": _dense(ks[4], d, d, g.dtype),
        "w1": _dense(ks[5], d, g.d_ff, g.dtype),
        "w2": _dense(ks[6], g.d_ff, d, g.dtype),
    }


@partial(jax.jit, static_argnums=(1,))
def layer_weights(layer_key, g: Geometry) -> Dict[str, Dict[str, jax.Array]]:
    """Both streams of one block (biases are zero, norm gains are one)."""
    k1, k2 = jax.random.split(layer_key)
    return {"img": _stream_weights(k1, g), "txt": _stream_weights(k2, g)}


@partial(jax.jit, static_argnums=(1,))
def outer_weights(key, g: Geometry) -> Dict[str, jax.Array]:
    """Embeddings and the final head; ``layers`` holds the per-layer keys."""
    ks = _split(key, 8)
    d = g.d_model
    return {
        "layers": jax.random.split(ks[0], g.n_layers),
        "patch_embed": _dense(ks[1], g.in_dim, d, g.dtype),
        "text_proj": _dense(ks[2], g.text_dim, d, g.dtype),
        "t_mlp1": _dense(ks[3], 256, d, g.dtype),
        "t_mlp2": _dense(ks[4], d, d, g.dtype),
        "final_ada": _dense(ks[5], d, 2 * d, g.dtype, scale=0.02),
        "final_proj": _dense(ks[6], d, g.in_dim, g.dtype),
    }


# ------------------------------------------------------------------ forward

def rms_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def timestep_embedding(t: jax.Array, dim: int = 256) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t[:, None].astype(F32) * freqs[None] * 1000.0
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def patchify(lat: jax.Array, p: int) -> jax.Array:
    b, h, w, c = lat.shape
    x = lat.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def unpatchify(x: jax.Array, p: int, size: int, ch: int) -> jax.Array:
    b, g = x.shape[0], size // p
    x = x.reshape(b, g, g, p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, size, size, ch)


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


def _qkv(p, x, t_emb, n_heads, fp8):
    s1, g1, m1, s2, g2, m2 = jnp.split(
        matmul(jax.nn.silu(t_emb), p["ada"], fp8), 6, -1)
    h = rms_norm(x) * (1 + g1[:, None]) + s1[:, None]
    b, s, d = h.shape
    heads = lambda w: matmul(h, w, fp8).reshape(b, s, n_heads, d // n_heads)
    return heads(p["wq"]), heads(p["wk"]), heads(p["wv"]), (m1, s2, g2, m2)


def _post(p, x, attn, mods, fp8):
    m1, s2, g2, m2 = mods
    b, s = attn.shape[:2]
    x = x + (1 + m1[:, None]) * matmul(attn.reshape(b, s, -1), p["wo"], fp8)
    h = rms_norm(x) * (1 + g2[:, None]) + s2[:, None]
    return x + (1 + m2[:, None]) * matmul(
        jax.nn.gelu(matmul(h, p["w1"], fp8), approximate=True), p["w2"], fp8)


@partial(jax.jit, static_argnums=(4, 5))
def block(w, x, c, t_emb, n_heads: int, fp8: bool = False):
    qi, ki, vi, mi = _qkv(w["img"], x, t_emb, n_heads, fp8)
    qt, kt, vt, mt = _qkv(w["txt"], c, t_emb, n_heads, fp8)
    out = attention(jnp.concatenate([qt, qi], 1), jnp.concatenate([kt, ki], 1),
                    jnp.concatenate([vt, vi], 1))
    tc = c.shape[1]
    return (_post(w["img"], x, out[:, tc:], mi, fp8),
            _post(w["txt"], c, out[:, :tc], mt, fp8))


@partial(jax.jit, static_argnums=(4,))
def _embed(o, lat, t, emb, g: Geometry):
    x = patchify(lat, g.patch) @ o["patch_embed"]
    c = emb @ o["text_proj"]
    t_emb = jax.nn.silu(timestep_embedding(t) @ o["t_mlp1"]) @ o["t_mlp2"]
    return x, c, t_emb


@partial(jax.jit, static_argnums=(3,))
def _head(o, x, t_emb, g: Geometry):
    shift, scale = jnp.split(jax.nn.silu(t_emb) @ o["final_ada"], 2, -1)
    x = rms_norm(x) * (1 + scale[:, None]) + shift[:, None]
    return unpatchify(x @ o["final_proj"], g.patch, g.latent_size,
                      g.latent_channels)


def velocity(g: Geometry, lat: jax.Array, t: jax.Array,
             emb: jax.Array, fp8: bool = False) -> jax.Array:
    """Backbone prediction for rows ``lat`` [B,S,S,C] at times ``t`` [B]
    under text embeddings ``emb`` [B,Tc,text_dim]; float32, with the
    blocks' projections computed in float8 when ``fp8`` (the control)."""
    with jax.default_matmul_precision("highest"):
        o = outer_weights(model_key(f"backbone:{g.family}"), g)
        x, c, t_emb = _embed(o, lat.astype(F32), t.astype(F32),
                             emb.astype(F32), g)
        for lk in o["layers"]:
            x, c = block(layer_weights(lk, g), x, c, t_emb, g.n_heads, fp8)
        return _head(o, x, t_emb, g)


def guided_step(g: Geometry, lat: jax.Array, emb: jax.Array, t_cur: float,
                t_next: float, guidance: float, fp8: bool = False
                ) -> jax.Array:
    """One Euler step of one request [1,S,S,C] with classifier-free
    guidance: the conditional and unconditional rows run as a batch of 2."""
    lat = lat.astype(F32)
    v = velocity(g, jnp.concatenate([lat, lat]),
                 jnp.full((2,), t_cur, F32),
                 jnp.concatenate([emb, jnp.zeros_like(emb)]).astype(F32), fp8)
    v_c, v_u = v[:1], v[1:]
    return lat + (t_next - t_cur) * (v_u + guidance * (v_c - v_u))


def flow_schedule(steps: int) -> np.ndarray:
    """Rectified-flow times t_0 = 1 ... t_steps = 0, in float32."""
    return np.linspace(1.0, 0.0, steps + 1).astype(np.float32)


def initial_latents(g: Geometry, seed: int) -> jax.Array:
    """The noise a request with input ``seed`` starts from."""
    return jax.random.normal(
        jax.random.PRNGKey(int(seed)),
        (1, g.latent_size, g.latent_size, g.latent_channels))


def sample(g: Geometry, lat: jax.Array, emb: jax.Array, steps: int,
           guidance: float, start: int = 0, stop: Optional[int] = None,
           fp8: bool = False) -> jax.Array:
    """Steps ``start`` .. ``stop`` of a ``steps``-step schedule."""
    sched = flow_schedule(steps)
    for i in range(start, steps if stop is None else stop):
        lat = guided_step(g, lat, emb, float(sched[i]), float(sched[i + 1]),
                          guidance, fp8)
    return lat


# ------------------------------------------------------------------ counts
# counted as ``chipbench/flops.py`` says: a multiply-add is 2 FLOPs, and
# elementwise work is left out

def rows_per_step(g: Geometry) -> int:
    """Both rows of classifier-free guidance."""
    return 2


def layer_flops(g: Geometry) -> Dict[str, float]:
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


def row_step_flops(g: Geometry) -> float:
    """One backbone forward of one CFG row: every block, the patch,
    text and timestep embeddings and the final adaLN and head."""
    layer = sum(layer_flops(g).values())
    d = g.d_model
    embed = 2 * (g.image_tokens * g.in_dim * d + g.text_tokens * g.text_dim * d
                 + 256 * d + d * d)
    head = 2 * (d * 2 * d + g.image_tokens * d * g.in_dim)
    return g.n_layers * layer + embed + head


def attention_calls(g: Geometry) -> List[Tuple[float, float]]:
    """One joint attention call per block."""
    return [(flash_attn_flops(g), flash_attn_bytes(g))] * g.n_layers


# ------------------------------------------------- the program's config

REDUCIBLE = {"num_layers": "n_layers"}


def program_fields(g: Geometry) -> Dict[str, Any]:
    """The program's ``DiTConfig`` at these sizes."""
    return dict(d_model=g.d_model, n_layers=g.n_layers, n_heads=g.n_heads,
                d_ff=g.d_ff, text_dim=g.text_dim, latent_size=g.latent_size,
                latent_channels=g.latent_channels, patch=g.patch,
                text_tokens=g.text_tokens, dtype=getattr(jnp, g.dtype))
