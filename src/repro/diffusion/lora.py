"""LoRA adapters for the MMDiT backbone (§2.1, weight-patching adapters).

A LoRA targets the image-stream attention projections of every two-stream
block (every MMDiT block; a FLUX backbone's double blocks):
``W' = W + scale * A @ B`` with ``A: [L, d, r]``, ``B: [L, r, d]``.

Two application modes:

* :func:`fold_lora` — functional weight update (the TPU-idiomatic analogue
  of Katz's in-place GPU hot-patching; used when a request's adapter future
  resolves mid-workflow);
* the fused :mod:`repro.kernels.lora_matmul` kernel — computes
  ``xW + s(xA)B`` without materializing ``W'`` in HBM, which keeps a
  *shared* base-model replica clean while serving per-request LoRAs.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.quant_matmul.ops import (
    dequantize_weight,
    is_quantized,
    quantize_weight,
)
from repro.nn.layers import quant_mode, split

Params = Dict[str, Any]

TARGETS = ("wq", "wk", "wv", "wo")

# ------------------------------------------------- quantization awareness
#
# Quantize-on-fold: base weights and adapter factors may arrive as
# QuantizedParams dicts (REPRO_QUANT).  Folding dequantizes the target,
# applies the low-rank delta in f32, and REquantizes in the same mode as
# the base — so the fold cache keeps the ~4x smaller representation and
# a folded placement costs quantized bytes, not fp32 bytes.

_FACTOR_KEYS = tuple(f"{t}_{s}" for t in TARGETS for s in ("a", "b"))


def _mode_of(q: Params) -> str:
    import jax.numpy as _jnp

    return "int8" if q["qw"].dtype == _jnp.int8 else "fp8"


def _requant_like(w: jax.Array, base) -> Any:
    """Quantize ``w`` the way ``base`` was quantized (identity if the
    base is a plain array)."""
    if is_quantized(base):
        return quantize_weight(w, _mode_of(base))
    return w.astype(base.dtype)


def quantize_lora(lora: Params) -> Params:
    """Quantize a backbone adapter's A/B factors per the active
    ``REPRO_QUANT`` mode (identity when off) — the AdapterPool and the
    proc-plane adapter ships then carry int8/fp8 factors."""
    mode = quant_mode()
    if mode == "off":
        return lora
    return {k: (quantize_weight(v, mode) if k in _FACTOR_KEYS else v)
            for k, v in lora.items()}


def quantize_text_lora(tl: Params) -> Params:
    """Quantized-factor form of a text-encoder adapter (see
    :func:`quantize_lora`)."""
    mode = quant_mode()
    if mode == "off":
        return tl
    return {k: (quantize_weight(v, mode) if k in ("a", "b") else v)
            for k, v in tl.items()}


def init_lora(key: jax.Array, cfg: Any, rank: int = 8,
              scale: float = 1.0) -> Params:
    d, n = cfg.d_model, cfg.adapter_layers
    ks = split(key, 2 * len(TARGETS))
    p: Params = {"scale": jnp.asarray(scale, cfg.dtype)}
    for i, t in enumerate(TARGETS):
        p[f"{t}_a"] = (
            jax.random.normal(ks[2 * i], (n, d, rank), dtype=jnp.float32)
            * (1.0 / jnp.sqrt(d))
        ).astype(cfg.dtype)
        p[f"{t}_b"] = jnp.zeros((n, rank, d), cfg.dtype)
    return p


def randomize_lora(key: jax.Array, lora: Params, amplitude: float = 0.02) -> Params:
    """Give the zero-init B matrices content (for tests/examples)."""
    out = dict(lora)
    for t in TARGETS:
        key, sub = jax.random.split(key)
        out[f"{t}_b"] = (
            jax.random.normal(sub, lora[f"{t}_b"].shape, dtype=jnp.float32) * amplitude
        ).astype(lora[f"{t}_b"].dtype)
    return out


def fold_lora(params: Params, lora: Params) -> Params:
    """Return backbone params with the LoRA folded into the image-stream
    attention weights.  Purely functional — the original pytree is intact,
    so a shared replica can serve other requests concurrently."""
    scale = lora["scale"]
    new_layers = dict(params["layers"])
    new_img = dict(new_layers["img"])
    for t in TARGETS:
        a = dequantize_weight(lora[f"{t}_a"])
        b = dequantize_weight(lora[f"{t}_b"])
        delta = jnp.einsum("ldr,lre->lde", a, b) * scale
        base = new_layers["img"][t]
        if is_quantized(base):
            new_img[t] = _requant_like(dequantize_weight(base) + delta, base)
        else:
            new_img[t] = base + delta.astype(base.dtype)
    new_layers["img"] = new_img
    out = dict(params)
    out["layers"] = new_layers
    return out


def unfold_lora(params: Params, lora: Params) -> Params:
    """Inverse of :func:`fold_lora` (restore the pristine base weights)."""
    neg = dict(lora)
    neg["scale"] = -lora["scale"]
    return fold_lora(params, neg)


def _pad_rank(x: jax.Array, axis: int, rank: int) -> jax.Array:
    pad = rank - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)      # zero rank columns contribute exactly 0


def stack_loras(loras: Sequence[Params]) -> Params:
    """Stack G per-adapter LoRA params into the grouped layout the
    batched multi-LoRA forward consumes.

    Adapters with different ranks are zero-padded to the largest rank
    (exact: extra zero columns of A / rows of B contribute nothing).
    Returns, per target ``t``:

    * ``{t}_a``: ``[L, G, d, r]`` and ``{t}_b``: ``[L, G, r, d]`` — the
      layer axis LEADS so the stacks ride the mmdit layer scan's xs
      (each scan step sees this layer's ``[G, d, r]`` factors);
    * ``scales``: ``[G]`` (closed over, not scanned).
    """
    if not loras:
        raise ValueError("stack_loras needs at least one adapter")
    loras = [{k: (dequantize_weight(v) if k in _FACTOR_KEYS else v)
              for k, v in p.items()} for p in loras]
    rank = max(p[f"{TARGETS[0]}_a"].shape[-1] for p in loras)
    out: Params = {
        "scales": jnp.stack([jnp.asarray(p["scale"], jnp.float32)
                             for p in loras]),
    }
    for t in TARGETS:
        a = jnp.stack([_pad_rank(p[f"{t}_a"], 2, rank) for p in loras])
        b = jnp.stack([_pad_rank(p[f"{t}_b"], 1, rank) for p in loras])
        out[f"{t}_a"] = a.transpose(1, 0, 2, 3)     # [G,L,d,r] -> [L,G,d,r]
        out[f"{t}_b"] = b.transpose(1, 0, 2, 3)     # [G,L,r,d] -> [L,G,r,d]
    return out


# ------------------------------------------------- text-encoder adapters
#
# A lightweight companion to the backbone LoRA: a low-rank delta on the
# LAST text-encoder layer's output projection (``wo``).  Folding adds
# ``scale * a @ b`` to that weight; the grouped path applies it per row.

def init_text_lora(key: jax.Array, d_model: int, rank: int = 8,
                   scale: float = 1.0, amplitude: float = 0.02,
                   dtype: Any = jnp.float32) -> Params:
    ka, kb = jax.random.split(key)
    return {
        "a": (jax.random.normal(ka, (d_model, rank), dtype=jnp.float32)
              * (1.0 / jnp.sqrt(d_model))).astype(dtype),
        "b": (jax.random.normal(kb, (rank, d_model), dtype=jnp.float32)
              * amplitude).astype(dtype),
        "scale": jnp.asarray(scale, dtype),
    }


def fold_text_lora(params: Params, tl: Params, sign: float = 1.0) -> Params:
    """Text-encoder params with the adapter folded into the last layer's
    ``wo`` (functional)."""
    delta = (dequantize_weight(tl["a"]) @ dequantize_weight(tl["b"])) \
        * tl["scale"] * sign
    layers = list(params["layers"])
    last = dict(layers[-1])
    wo = last["wo"]
    if is_quantized(wo):
        last["wo"] = _requant_like(dequantize_weight(wo) + delta, wo)
    else:
        last["wo"] = wo + delta.astype(wo.dtype)
    layers[-1] = last
    out = dict(params)
    out["layers"] = layers
    return out


def stack_text_loras(tls: Sequence[Params]) -> Params:
    """Stack G text-encoder adapters: ``a [G,d,r]``, ``b [G,r,d]``,
    ``scales [G]`` (ranks zero-padded to the largest)."""
    if not tls:
        raise ValueError("stack_text_loras needs at least one adapter")
    tls = [{k: (dequantize_weight(v) if k in ("a", "b") else v)
            for k, v in p.items()} for p in tls]
    rank = max(p["a"].shape[-1] for p in tls)
    return {
        "a": jnp.stack([_pad_rank(p["a"], 1, rank) for p in tls]),
        "b": jnp.stack([_pad_rank(p["b"], 0, rank) for p in tls]),
        "scales": jnp.stack([jnp.asarray(p["scale"], jnp.float32)
                             for p in tls]),
    }
