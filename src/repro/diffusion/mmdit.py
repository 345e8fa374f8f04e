"""MMDiT diffusion backbone (SD3/Flux-style) in pure JAX.

Joint text-image attention transformer with adaLN timestep modulation
[Esser et al. 2024].  Layers are *stacked* and iterated with
``jax.lax.scan`` so the compiled HLO contains each block once — essential
for the multi-pod dry-runs.

The same block stack doubles as the ControlNet branch
(:func:`init_controlnet` / :func:`controlnet_apply`): a truncated copy of
the backbone whose per-layer image-stream states are projected through
zero-initialized denses into additive residuals, which
:func:`mmdit_apply` injects after the corresponding backbone layers —
exactly the fan-in dataflow whose cross-GPU scheduling LegoDiffusion's
deferred fetch exists to support.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.diffusion.config import DiTConfig
from repro.nn.layers import (
    dense_init,
    flash_attention_enabled,
    gqa_attention,
    grouped_lora_dense,
    modulate,
    qdense,
    quantize_dense,
    rms_norm,
    shard_map_compat,
    split,
    timestep_embedding,
)

Params = Dict[str, Any]


# ------------------------------------------------------------------ blocks

def _init_stream(key: jax.Array, cfg: DiTConfig) -> Params:
    d, dff = cfg.d_model, cfg.d_ff
    ks = split(key, 8)
    return {
        "ada": dense_init(ks[0], d, 6 * d, cfg.dtype, scale=0.02),
        "ada_b": jnp.zeros((6 * d,), cfg.dtype),
        "norm1": jnp.ones((d,), cfg.dtype),
        "wq": dense_init(ks[1], d, d, cfg.dtype),
        "wk": dense_init(ks[2], d, d, cfg.dtype),
        "wv": dense_init(ks[3], d, d, cfg.dtype),
        "wo": dense_init(ks[4], d, d, cfg.dtype),
        "norm2": jnp.ones((d,), cfg.dtype),
        "w1": dense_init(ks[5], d, dff, cfg.dtype),
        "w2": dense_init(ks[6], dff, d, cfg.dtype),
    }


def init_layer(key: jax.Array, cfg: DiTConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {"img": _init_stream(k1, cfg), "txt": _init_stream(k2, cfg)}


def _lora_proj(h: jax.Array, w: jax.Array, lora, target: str) -> jax.Array:
    """``h @ w``, or the grouped per-row multi-LoRA projection when this
    layer carries adapter stacks for ``target``.  ``lora`` is
    ``(layer_stacks, idx, scales)`` with ``layer_stacks[f"{target}_a"]``
    ``[G, d, r]`` / ``..._b`` ``[G, r, d]``."""
    if lora is None:
        return qdense(h, w)
    stacks, idx, scales = lora
    return grouped_lora_dense(h, w, stacks[f"{target}_a"],
                              stacks[f"{target}_b"], idx, scales)


def _stream_qkv(p: Params, x: jax.Array, t_emb: jax.Array, n_heads: int,
                lora=None):
    ada = qdense(jax.nn.silu(t_emb), p["ada"]) + p["ada_b"]
    (s1, g1, m1, s2, g2, m2) = jnp.split(ada, 6, axis=-1)
    m1 = 1.0 + m1          # gate baseline: identity-plus-delta
    m2 = 1.0 + m2
    h = modulate(rms_norm(x, p["norm1"]), s1, g1).astype(x.dtype)
    b, s, d = h.shape
    hd = d // n_heads
    q = _lora_proj(h, p["wq"], lora, "wq").reshape(b, s, n_heads, hd)
    k = _lora_proj(h, p["wk"], lora, "wk").reshape(b, s, n_heads, hd)
    v = _lora_proj(h, p["wv"], lora, "wv").reshape(b, s, n_heads, hd)
    return q, k, v, (m1, s2, g2, m2)


def _stream_post(p: Params, x: jax.Array, attn_out: jax.Array, mods, n_heads: int,
                 lora=None):
    m1, s2, g2, m2 = mods
    b, s, _, _ = attn_out.shape
    # keep the residual stream in the param dtype (t_emb gates are f32)
    proj = _lora_proj(attn_out.reshape(b, s, -1), p["wo"], lora, "wo")
    x = x + (m1[:, None, :] * proj).astype(x.dtype)
    h = modulate(rms_norm(x, p["norm2"]), s2, g2).astype(x.dtype)
    x = x + (m2[:, None, :] * qdense(jax.nn.gelu(qdense(h, p["w1"])),
                                     p["w2"])).astype(x.dtype)
    return x


def mmdit_block(
    p: Params,
    x: jax.Array,            # image tokens [B, Ti, d]
    c: jax.Array,            # text tokens  [B, Tc, d]
    t_emb: jax.Array,        # [B, d]
    n_heads: int,
    lora=None,               # (layer adapter stacks, idx [B], scales [G])
) -> Tuple[jax.Array, jax.Array]:
    qi, ki, vi, mods_i = _stream_qkv(p["img"], x, t_emb, n_heads, lora=lora)
    qt, kt, vt, mods_t = _stream_qkv(p["txt"], c, t_emb, n_heads)
    q = jnp.concatenate([qt, qi], axis=1)
    k = jnp.concatenate([kt, ki], axis=1)
    v = jnp.concatenate([vt, vi], axis=1)
    out = gqa_attention(q, k, v, causal=False)
    tc = c.shape[1]
    out_t, out_i = out[:, :tc], out[:, tc:]
    x = _stream_post(p["img"], x, out_i, mods_i, n_heads, lora=lora)
    c = _stream_post(p["txt"], c, out_t, mods_t, n_heads)
    return x, c


# ------------------------------------------------------------ quantization

# the per-layer stream projections carry essentially all backbone
# parameters; embeds / final head stay fp32 (tiny, I/O-critical)
_QUANT_STREAM_KEYS = ("ada", "wq", "wk", "wv", "wo", "w1", "w2")


def quantize_mmdit_params(params: Params) -> Params:
    """Quantize the layer-stacked stream projection weights per the
    active ``REPRO_QUANT`` mode (identity when off).  The quantized
    dicts replace the plain arrays in-place in a copied tree, so they
    ride the layer scan's xs exactly like the fp32 weights did."""
    layers = params.get("layers")
    if layers is None:
        return params
    new_layers = {}
    for stream, sp in layers.items():
        if not isinstance(sp, dict):
            new_layers[stream] = sp
            continue
        new_layers[stream] = {
            k: (quantize_dense(v) if k in _QUANT_STREAM_KEYS else v)
            for k, v in sp.items()
        }
    out = dict(params)
    out["layers"] = new_layers
    return out


# ---------------------------------------------------------------- backbone

def init_mmdit(key: jax.Array, cfg: DiTConfig) -> Params:
    ks = split(key, 8)
    d = cfg.d_model
    in_dim = cfg.patch * cfg.patch * cfg.latent_channels
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    return {
        "patch_embed": dense_init(ks[1], in_dim, d, cfg.dtype),
        "text_proj": dense_init(ks[2], cfg.text_dim, d, cfg.dtype),
        "t_mlp1": dense_init(ks[3], 256, d, cfg.dtype),
        "t_mlp2": dense_init(ks[4], d, d, cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "final_ada": dense_init(ks[5], d, 2 * d, cfg.dtype, scale=0.02),
        "final_ada_b": jnp.zeros((2 * d,), cfg.dtype),
        "final_proj": dense_init(ks[6], d, in_dim, cfg.dtype),
    }


def patchify(latents: jax.Array, patch: int) -> jax.Array:
    b, h, w, ch = latents.shape
    x = latents.reshape(b, h // patch, patch, w // patch, patch, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // patch) * (w // patch), patch * patch * ch)


def unpatchify(tokens: jax.Array, patch: int, size: int, channels: int) -> jax.Array:
    b = tokens.shape[0]
    g = size // patch
    x = tokens.reshape(b, g, g, patch, patch, channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, size, size, channels)


def _embed_inputs(params: Params, cfg: DiTConfig, latents, t, text_emb):
    # token streams run in the param dtype whatever the sampler state and
    # the text encoder emit (f32 latents into a bf16 backbone)
    x = patchify(latents, cfg.patch).astype(cfg.dtype) @ params["patch_embed"]
    c = text_emb.astype(cfg.dtype) @ params["text_proj"]
    t_emb = timestep_embedding(t, 256)
    t_emb = jax.nn.silu(t_emb @ params["t_mlp1"]) @ params["t_mlp2"]
    return x, c, t_emb


def mmdit_apply(
    params: Params,
    cfg: DiTConfig,
    latents: jax.Array,                       # [B, S, S, C]
    t: jax.Array,                             # [B]
    text_emb: jax.Array,                      # [B, Tc, text_dim]
    control_residuals: Optional[jax.Array] = None,   # [L, B, Ti, d] (padded)
    lora_stack: Optional[Params] = None,      # stack_loras output ([L,G,...])
    lora_idx: Optional[jax.Array] = None,     # [B] int32; -1 = no adapter
) -> jax.Array:
    """One denoising forward pass; returns the velocity/noise prediction.

    When ``lora_stack``/``lora_idx`` are given, the image-stream attention
    projections run the grouped multi-adapter form: each batch row applies
    its own LoRA (``lora_idx[b]``) against the shared base weights.  The
    layer-leading adapter stacks ride the layer scan's xs alongside the
    params, so the whole multi-tenant forward stays one jitted scan."""
    x, c, t_emb = _embed_inputs(params, cfg, latents, t, text_emb)
    if control_residuals is None:
        control_residuals = jnp.zeros(
            (cfg.n_layers,) + x.shape, dtype=x.dtype
        )

    if lora_stack is None:
        scales = idx = None
        lora_xs = None
    else:
        scales = lora_stack["scales"]
        idx = lora_idx.astype(jnp.int32)
        lora_xs = {k: v for k, v in lora_stack.items() if k != "scales"}

    def body(carry, xs):
        x, c = carry
        if lora_xs is None:
            layer_p, res = xs
            lora = None
        else:
            layer_p, res, layer_lora = xs
            lora = (layer_lora, idx, scales)
        x, c = mmdit_block(layer_p, x, c, t_emb, cfg.n_heads, lora=lora)
        x = x + res
        return (x, c), None

    xs = ((params["layers"], control_residuals) if lora_xs is None
          else (params["layers"], control_residuals, lora_xs))
    (x, c), _ = jax.lax.scan(body, (x, c), xs)
    ada = jax.nn.silu(t_emb) @ params["final_ada"] + params["final_ada_b"]
    shift, scale = jnp.split(ada, 2, axis=-1)
    x = modulate(rms_norm(x, params["final_norm"]), shift, scale)
    out = x @ params["final_proj"]
    return unpatchify(out, cfg.patch, cfg.latent_size, cfg.latent_channels)


# ------------------------------------------------- sequence-sharded backbone

def _mmdit_block_seq(
    p: Params,
    x: jax.Array,            # LOCAL image tokens [B, Ti/k, d]
    c: jax.Array,            # replicated text tokens [B, Tc, d]
    t_emb: jax.Array,
    n_heads: int,
    axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """One MMDiT block under sequence sharding: each device holds a
    contiguous slice of the image tokens; joint attention stays exact by
    all-gathering the image K/V (one tiled collective per stream per
    layer), after which local queries — text plus the local image slice —
    run through the same attention route as the unsharded block (the
    Pallas flash kernel handles the rectangular local-q × global-kv
    shape).  The text stream sees only replicated/gathered operands, so it
    stays bitwise-replicated across the mesh without a second collective.
    """
    qi, ki, vi, mods_i = _stream_qkv(p["img"], x, t_emb, n_heads)
    qt, kt, vt, mods_t = _stream_qkv(p["txt"], c, t_emb, n_heads)
    ki = jax.lax.all_gather(ki, axis, axis=1, tiled=True)
    vi = jax.lax.all_gather(vi, axis, axis=1, tiled=True)
    q = jnp.concatenate([qt, qi], axis=1)          # [B, Tc + Ti/k, H, hd]
    k = jnp.concatenate([kt, ki], axis=1)          # [B, Tc + Ti,   H, hd]
    v = jnp.concatenate([vt, vi], axis=1)
    # the Pallas kernel's padding-guarded k-sweep handles the rectangular
    # local-q x global-kv shape natively, so the sharded path keeps the
    # same attention route as the unsharded block
    from repro.kernels.flash_attention.ops import mha

    out = mha(q, k, v, causal=False, use_kernel=flash_attention_enabled())
    tc = c.shape[1]
    out_t, out_i = out[:, :tc], out[:, tc:]
    x = _stream_post(p["img"], x, out_i, mods_i, n_heads)
    c = _stream_post(p["txt"], c, out_t, mods_t, n_heads)
    return x, c


def seq_shard_divisor(cfg: Any, k: int) -> bool:
    """Can the latent's patch-row grid split evenly across k devices for
    the sequence-sharded MMDiT forward?  Never for another architecture:
    FLUX has no sequence-sharded forward."""
    return (cfg.architecture == "mmdit"
            and (cfg.latent_size // cfg.patch) % k == 0)


def mmdit_apply_seq_sharded(
    params: Params,
    cfg: DiTConfig,
    latents: jax.Array,                       # [B, S, S, C]
    t: jax.Array,                             # [B]
    text_emb: jax.Array,                      # [B, Tc, text_dim]
    control_residuals: Optional[jax.Array],   # [L, B, Ti, d] (padded)
    mesh: Any,
) -> jax.Array:
    """Sequence-sharded denoising forward on a device mesh (§5.2).

    The latent's spatial rows (equivalently, contiguous image-token
    chunks — patchify is row-major over the patch grid) are sharded
    across the mesh axis; parameters, timesteps and text embeddings are
    replicated.  Per layer the image K/V are all-gathered so attention is
    exact; everything else is token-local.  Composes with batches of ANY
    size — the path adaptive parallelism needs when a batch has fewer
    rows than the submesh has devices (e.g. one CFG pair on k=4).
    """
    axis = mesh.axis_names[0]
    if control_residuals is None:
        b = latents.shape[0]
        control_residuals = jnp.zeros(
            (cfg.n_layers, b, cfg.image_tokens, cfg.d_model), cfg.dtype)

    def shard_fn(params, lat, t, emb, res):
        # same embedding as the unsharded forward; patchify sees only this
        # shard's latent rows, so x holds the local token slice
        x, c, t_emb = _embed_inputs(params, cfg, lat, t, emb)

        def body(carry, xs):
            x, c = carry
            layer_p, r = xs
            x, c = _mmdit_block_seq(layer_p, x, c, t_emb, cfg.n_heads, axis)
            x = x + r
            return (x, c), None

        (x, c), _ = jax.lax.scan(body, (x, c),
                                 (params["layers"], res))
        ada = jax.nn.silu(t_emb) @ params["final_ada"] + params["final_ada_b"]
        shift, scale = jnp.split(ada, 2, axis=-1)
        x = modulate(rms_norm(x, params["final_norm"]), shift, scale)
        out = x @ params["final_proj"]
        # local unpatchify: this shard's token rows -> its latent rows
        b = out.shape[0]
        g = cfg.latent_size // cfg.patch              # global patch columns
        rows = out.shape[1] // g                      # local patch rows
        o = out.reshape(b, rows, g, cfg.patch, cfg.patch, cfg.latent_channels)
        o = o.transpose(0, 1, 3, 2, 4, 5)
        return o.reshape(b, rows * cfg.patch, cfg.latent_size,
                         cfg.latent_channels)

    from jax.sharding import PartitionSpec as P

    fn = shard_map_compat(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(), P(), P(None, None, axis)),
        out_specs=P(None, axis),
    )
    return fn(params, latents, t, text_emb, control_residuals)


# -------------------------------------------------------------- ControlNet

def init_controlnet(key: jax.Array, cfg: DiTConfig, n_cn_layers: Optional[int] = None) -> Params:
    """ControlNet branch: truncated backbone copy + zero-init out projs."""
    n_cn = n_cn_layers or max(1, cfg.n_layers // 2)
    ks = split(key, 3)
    base = init_mmdit(ks[0], cfg)
    layer_keys = jax.random.split(ks[1], n_cn)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    d = cfg.d_model
    # small (not zero) residual projections so the executable plane is
    # non-degenerate; true zero-init is a training-time concern
    zero_proj = (jax.random.normal(split(ks[2], 2)[0], (n_cn, d, d),
                                   dtype=jnp.float32) * 0.02).astype(cfg.dtype)
    return {
        "patch_embed": base["patch_embed"],
        "cond_embed": dense_init(ks[2], cfg.patch * cfg.patch * cfg.latent_channels,
                                 d, cfg.dtype, scale=0.0),
        "text_proj": base["text_proj"],
        "t_mlp1": base["t_mlp1"],
        "t_mlp2": base["t_mlp2"],
        "layers": layers,
        "zero_proj": zero_proj,
    }


def controlnet_apply(
    params: Params,
    cfg: DiTConfig,
    latents: jax.Array,          # current noisy latents [B,S,S,C]
    cond_latents: jax.Array,     # VAE-encoded reference image [B,S,S,C]
    t: jax.Array,
    text_emb: jax.Array,
) -> jax.Array:
    """Returns residuals [n_layers, B, Ti, d], zero-padded to full depth."""
    x = patchify(latents, cfg.patch) @ params["patch_embed"]
    x = x + patchify(cond_latents, cfg.patch) @ params["cond_embed"]
    c = text_emb @ params["text_proj"]
    t_emb = timestep_embedding(t, 256)
    t_emb = jax.nn.silu(t_emb @ params["t_mlp1"]) @ params["t_mlp2"]

    def body(carry, xs):
        x, c = carry
        layer_p, zproj = xs
        x, c = mmdit_block(layer_p, x, c, t_emb, cfg.n_heads)
        return (x, c), x @ zproj

    (_, _), residuals = jax.lax.scan(
        body, (x, c), (params["layers"], params["zero_proj"])
    )
    n_cn = residuals.shape[0]
    if n_cn < cfg.n_layers:
        pad = jnp.zeros((cfg.n_layers - n_cn,) + residuals.shape[1:], residuals.dtype)
        residuals = jnp.concatenate([residuals, pad], axis=0)
    return residuals
