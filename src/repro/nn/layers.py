"""Shared pure-JAX neural-net primitives.

Used by both the diffusion substrate (:mod:`repro.diffusion`) and the
assigned-architecture zoo (:mod:`repro.models`).  Everything is functional:
``init_*`` builds parameter pytrees, ``*_apply``-style functions consume
them.  No framework dependencies — plain ``jax.numpy`` + ``jax.lax``.
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


Params = Dict[str, Any]


# ------------------------------------------------- flash-attention routing
#
# The non-causal, mask-free attention path (the MMDiT joint text+image
# hot path, the text encoder) routes through ``mha`` of
# repro.kernels.flash_attention: the Pallas kernel — interpret mode on CPU,
# compiled Mosaic on TPU — or, under ``REPRO_FLASH_ATTENTION=0`` (or
# set_flash_attention(False)), the kernel's pure-jnp oracle.

_flash_enabled: bool = os.environ.get(
    "REPRO_FLASH_ATTENTION", "1").lower() not in ("0", "false", "off")


def set_flash_attention(enabled: bool) -> bool:
    """Toggle the Pallas flash-attention route; returns the previous value.

    The flag is read at TRACE time: ``jax.jit``-compiled functions keep
    whichever route was active when they were first traced.  Toggle before
    loading models (or load fresh components afterwards) for it to take
    effect on their jitted applies.
    """
    global _flash_enabled
    prev = _flash_enabled
    _flash_enabled = bool(enabled)
    return prev


def flash_attention_enabled() -> bool:
    return _flash_enabled


# ------------------------------------------------- quantized-forward routing
#
# The raw-speed plane's weight quantization (``REPRO_QUANT=int8|fp8|off``,
# default off).  Like the flash flag this is consulted when parameters are
# MATERIALIZED (model load / LoRA fold), not inside jitted applies: the
# applies are structure-driven — they meet a ``QuantizedParams`` dict
# (see :mod:`repro.kernels.quant_matmul.ops`) and take the quantized
# projection path, or a plain array and take the fp32 path.

_QUANT_MODES = ("off", "int8", "fp8")
_quant_mode: str = os.environ.get("REPRO_QUANT", "off").lower()
if _quant_mode in ("", "0", "false"):
    _quant_mode = "off"
if _quant_mode not in _QUANT_MODES:
    raise ValueError(
        f"REPRO_QUANT={_quant_mode!r}: expected one of {_QUANT_MODES}")


def set_quant_mode(mode: str) -> str:
    """Set the weight-quantization mode (``off``/``int8``/``fp8``);
    returns the previous mode.  Takes effect on the next model load or
    LoRA fold — already-materialized components keep their dtype."""
    global _quant_mode
    if mode not in _QUANT_MODES:
        raise ValueError(f"quant mode {mode!r}: expected one of {_QUANT_MODES}")
    prev = _quant_mode
    _quant_mode = mode
    return prev


def quant_mode() -> str:
    return _quant_mode


def quantize_dense(w: jax.Array):
    """Quantize one dense projection weight per the active mode (identity
    when ``off`` or already quantized)."""
    if _quant_mode == "off":
        return w
    from repro.kernels.quant_matmul.ops import quantize_weight

    return quantize_weight(w, _quant_mode)


def qdense(h: jax.Array, w) -> jax.Array:
    """Dense projection that accepts either a plain ``[d_in, d_out]``
    weight (fp32 matmul) or a QuantizedParams dict (quantized path)."""
    from repro.kernels.quant_matmul.ops import is_quantized, quant_apply

    if is_quantized(w):
        return quant_apply(h, w["qw"], w["qs"])
    return h @ w


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the bodies mix
    replicated and sharded operands by design)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ----------------------------------------------------- grouped LoRA dense

def grouped_lora_dense(
    h: jax.Array,               # [B, S, d_in]
    w: jax.Array,               # [d_in, d_out]
    a: jax.Array,               # [G, d_in, r]  stacked adapter A factors
    b: jax.Array,               # [G, r, d_out] stacked adapter B factors
    idx: jax.Array,             # [B] int32 adapter per batch row; -1 = none
    scales: jax.Array,          # [G]
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """Dense projection with a per-row grouped multi-LoRA delta:
    ``h @ w + scales[idx] * (h @ a[idx]) @ b[idx]`` — one forward serves a
    batch mixing G tenants.  Routes through the Pallas ``lora_matmul``
    grouped kernel on TPU (``repro.kernels.lora_matmul.ops`` gate), the
    jnp grouped oracle elsewhere; rows with ``idx < 0`` are bit-exactly
    the plain projection on the jnp route."""
    from repro.kernels.lora_matmul.ops import lora_apply_grouped
    from repro.kernels.quant_matmul.ops import dequantize_weight

    w = dequantize_weight(w)    # grouped kernel needs the dense base
    bsz, s, d_in = h.shape
    rows_idx = jnp.repeat(idx.astype(jnp.int32), s)
    out = lora_apply_grouped(h.reshape(bsz * s, d_in), w, a, b,
                             rows_idx, scales, use_kernel=use_kernel)
    return out.reshape(bsz, s, w.shape[1])


# ---------------------------------------------------------------- init utils

def dense_init(key: jax.Array, d_in: int, d_out: int, dtype: Any = jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype: Any = jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


def split(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


# -------------------------------------------------------------------- norms

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * w).astype(dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * w + b).astype(dtype)


# --------------------------------------------------------------------- RoPE

def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     dtype: Any = jnp.float32) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_seq)
    freqs = np.outer(t, inv)
    return jnp.asarray(np.cos(freqs), dtype), jnp.asarray(np.sin(freqs), dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """x: [..., seq, heads, head_dim]; cos/sin: [max_seq, head_dim/2]."""
    if positions is not None:
        cos = jnp.take(cos, positions, axis=0)
        sin = jnp.take(sin, positions, axis=0)
    else:
        cos = cos[: x.shape[-3]]
        sin = sin[: x.shape[-3]]
    # broadcast over head axis
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def _blockwise_attention(
    q: jax.Array,                 # [B, Sq, H, D] (kv already head-repeated)
    k: jax.Array,                 # [B, Sk, H, D]
    v: jax.Array,
    causal: bool,
    window: Optional[int],
    scale: float,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Blockwise online-softmax attention, pure jnp (the lax.scan analogue
    of the flash-attention kernel).  Keeps live memory at
    O(block_q x block_k) per head instead of O(S^2) — required for the
    32k/500k shapes to fit v5e HBM."""
    b, sq, h, d = q.shape
    _, sk, _, _ = k.shape
    pq, pk = (-sq) % block_q, (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (sq + pq) // block_q, (sk + pk) // block_k
    qb = q.reshape(b, nq, block_q, h, d).transpose(1, 0, 3, 2, 4)  # [nq,b,h,bq,d]
    kb = k.reshape(b, nk, block_k, h, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, block_k, h, d).transpose(1, 0, 3, 2, 4)
    neg = jnp.finfo(jnp.float32).min

    def q_block(qi, qtile):
        qtile = qtile.astype(jnp.float32) * scale
        qpos = qi * block_q + jnp.arange(block_q)[:, None]

        def k_block(carry, xs):
            m, l, acc = carry
            ki, ktile, vtile = xs
            s = jnp.einsum("bhqd,bhkd->bhqk", qtile, ktile.astype(jnp.float32))
            kpos = ki * block_k + jnp.arange(block_k)[None, :]
            mask = kpos < sk
            if causal:
                mask = mask & (kpos <= qpos)
            if window is not None:
                mask = mask & (kpos > qpos - window)
            s = jnp.where(mask[None, None], s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask[None, None], jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vtile.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((b, h, block_q), neg)
        l0 = jnp.zeros((b, h, block_q))
        a0 = jnp.zeros((b, h, block_q, d))
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]                      # [b,h,bq,d]

    out = jax.lax.map(lambda xs: q_block(*xs), (jnp.arange(nq), qb))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, sq + pq, h, d)
    return out[:, :sq].astype(q.dtype)


def _blockwise_decode(
    q: jax.Array,                 # [B, Sq<=128, Hq, D]
    k: jax.Array,                 # [B, Sk, Hkv, D]   (long cache)
    v: jax.Array,
    mask: jax.Array,              # [B, 1, Sq, Sk] additive
    scale: float,
    group: int,
    block_k: int = 2048,
) -> jax.Array:
    """Decode attention over a long KV cache, blockwise with online
    softmax.  The GQA head repeat and f32 upcast happen per K tile, so the
    32k-deep cache is never materialized repeated or in f32 — this is what
    keeps decode_32k inside v5e HBM for the 56-head archs (yi-34b)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    pk = (-sk) % block_k
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pk)),
                       constant_values=jnp.finfo(jnp.float32).min)
    nk = (sk + pk) // block_k
    kb = k.reshape(b, nk, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    mb = mask.reshape(b, 1, sq, nk, block_k).transpose(3, 0, 1, 2, 4)
    qf = q.astype(jnp.float32) * scale
    neg = jnp.finfo(jnp.float32).min

    def k_block(carry, xs):
        m, l, acc = carry
        ktile, vtile, mtile = xs              # [b,bk,hkv,d], [b,1,sq,bk]
        kt = jnp.repeat(ktile, group, axis=2).astype(jnp.float32)
        vt = jnp.repeat(vtile, group, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kt) + mtile
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(s <= neg / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bqhd", p, vt
                                                  ).transpose(0, 2, 1, 3)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hq, sq), neg)
    l0 = jnp.zeros((b, hq, sq))
    a0 = jnp.zeros((b, hq, sq, d))
    (m, l, acc), _ = jax.lax.scan(k_block, (m0, l0, a0), (kb, vb, mb))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]                  # [b,hq,sq,d]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def gqa_attention(
    q: jax.Array,                 # [B, Sq, Hq, D]
    k: jax.Array,                 # [B, Sk, Hkv, D]
    v: jax.Array,                 # [B, Sk, Hkv, D]
    causal: bool = False,
    window: Optional[int] = None,          # sliding-window size (causal)
    q_offset: int = 0,                     # absolute position of q[0]
    mask: Optional[jax.Array] = None,      # extra additive mask [B,1,Sq,Sk]
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention, pure jnp reference path.

    Supports GQA head grouping, causal masking, and sliding-window masking
    (the sub-quadratic decode variant used by danube/recurrentgemma and the
    long_500k SWA carve-out).
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    # non-causal, mask-free joint-sequence attention (the MMDiT hot path):
    # the Pallas flash-attention kernel, or its oracle when the flag routes
    # the reference.  Long sequences keep the dedicated blockwise paths.
    # (The sharded sequence-parallel rectangle — local queries against the
    # all-gathered K/V — calls ``mha`` directly; see _mmdit_block_seq.)
    if (not causal and window is None and mask is None
            and q_offset == 0 and softmax_scale is None and sq == sk
            and sq <= 8192):
        from repro.kernels.flash_attention.ops import mha

        return mha(q, k, v, causal=False, use_kernel=_flash_enabled)
    # decode against a long cache: grouped blockwise path (never
    # materializes the repeated-KV or the f32 full cache)
    if mask is not None and sk > 8192 and sq <= 128:
        return _blockwise_decode(q, k, v, mask, scale, group)
    # repeat KV to full query heads: keeps the head dim at hq (divisible by
    # the model axis) so GSPMD head-shards the O(S^2) logits tensor
    kr = jnp.repeat(k, group, axis=2) if group > 1 else k
    vr = jnp.repeat(v, group, axis=2) if group > 1 else v
    # long sequences: blockwise online-softmax path (O(block^2) live memory)
    if mask is None and sq > 8192:
        return _blockwise_attention(q, kr, vr, causal, window, scale)
    qf = q.astype(jnp.float32) * scale
    kf = kr.astype(jnp.float32)
    vf = vr.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    neg = jnp.finfo(jnp.float32).min
    if causal:
        logits = jnp.where((kpos > qpos)[None, None], neg, logits)
    if window is not None:
        logits = jnp.where((kpos <= qpos - window)[None, None], neg, logits)
    if mask is not None:
        logits = logits + mask  # [B, 1, Sq, Sk]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


def init_attention(key: jax.Array, d_model: int, n_heads: int, n_kv: int,
                   head_dim: Optional[int] = None, dtype: Any = jnp.float32,
                   qk_norm: bool = False) -> Params:
    head_dim = head_dim or d_model // n_heads
    ks = split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def attention_block(
    p: Params,
    x: jax.Array,                       # [B, S, d_model]
    n_heads: int,
    n_kv: int,
    rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Full attention sublayer with optional KV cache for decode.

    With ``kv_cache``/``cache_index``: writes this call's K/V at
    ``cache_index`` and attends over the whole cache with position masking.
    """
    b, s, _ = x.shape
    head_dim = p["wq"].shape[1] // n_heads
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(b, s, n_kv, head_dim)
    v = (x @ p["wv"]).reshape(b, s, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        idx = cache_index if cache_index is not None else 0
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), idx, axis=1)
        new_cache = (ck, cv)
        # mask out not-yet-written cache slots
        kpos = jnp.arange(ck.shape[1])
        valid = kpos < (idx + s)
        neg = jnp.finfo(jnp.float32).min
        amask = jnp.where(valid, 0.0, neg)[None, None, None, :]
        q_offset = idx
        out = gqa_attention(q, ck, cv, causal=False, window=None,
                            q_offset=q_offset, mask=jnp.broadcast_to(
                                amask, (b, 1, s, ck.shape[1])))
        if window is not None:
            # sliding window over absolute positions
            qpos = q_offset + jnp.arange(s)[:, None]
            wmask = jnp.where(kpos[None, :] <= qpos - window, neg, 0.0)
            out = gqa_attention(q, ck, cv, causal=False, q_offset=q_offset,
                                mask=(amask + wmask[None, None]).astype(jnp.float32))
    else:
        out = gqa_attention(q, k, v, causal=causal, window=window)
    out = out.reshape(b, s, n_heads * head_dim)
    return out @ p["wo"], new_cache


# --------------------------------------------------------------------- MLPs

def init_swiglu(key: jax.Array, d_model: int, d_ff: int, dtype: Any = jnp.float32) -> Params:
    ks = split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype: Any = jnp.float32) -> Params:
    ks = split(key, 2)
    return {
        "w1": dense_init(ks[0], d_model, d_ff, dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": dense_init(ks[1], d_ff, d_model, dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    return qdense(jax.nn.gelu(qdense(x, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]


# ------------------------------------------------------------- embeddings

def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0) -> jax.Array:
    """Sinusoidal embedding of diffusion timesteps; t: [B] float in [0,1]."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None] * 1000.0
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


def modulate(x: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    """adaLN modulation: x * (1+scale) + shift, broadcast over sequence."""
    return x * (1 + scale[:, None, :]) + shift[:, None, :]


def mask_vocab(logits: jax.Array, vocab: int) -> jax.Array:
    """Suppress padded vocab columns (finite -1e9, softmax-safe)."""
    vp = logits.shape[-1]
    if vp == vocab:
        return logits
    pad_mask = (jnp.arange(vp) >= vocab) * jnp.asarray(-1e9, logits.dtype)
    return logits + pad_mask
