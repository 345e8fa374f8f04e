"""Servable diffusion component models (the ``Model`` subclasses).

Every component of a T2I workflow is a :class:`~repro.core.model.Model`
subclass whose ``cost()`` carries the real-scale statistics (for profiles,
baselines, roofline) and whose ``load()/execute()`` run the JAX
implementation at the family's executable ``dit`` config (for the
executable plane): the CPU-sized toy by default, the published backbone
width under ``family.at_published_width()``.  The text encoder and VAE
are stand-ins at the widths the backbone's interface needs.

Workflow builders (Table 2's S1-S6) live in
:mod:`repro.diffusion.workflows`; ``repro.diffusion.serving`` re-exports
both for backwards compatibility.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.model import Model, ModelCost
from repro.core.types import Image, TensorType
from repro.diffusion.config import DiffusionFamily, DiTConfig
from repro.nn.layers import shard_map_compat
from repro.diffusion.encoders import (
    init_text_encoder,
    init_vae,
    quantize_text_params,
    stable_hash,
    text_encoder_apply,
    tokenize,
    tokenize_batch,
    vae_decode,
    vae_encode,
)
from repro.diffusion.lora import (
    fold_lora,
    fold_text_lora,
    init_lora,
    init_text_lora,
    quantize_lora,
    quantize_text_lora,
    randomize_lora,
    stack_loras,
    stack_text_loras,
)
from repro.diffusion.mmdit import (
    controlnet_apply,
    init_controlnet,
    init_mmdit,
    mmdit_apply,
    mmdit_apply_seq_sharded,
    quantize_mmdit_params,
    seq_shard_divisor,
)
from repro.diffusion.sampler import (
    cfg_combine,
    denoise_step_jit,
    donate_buffers_enabled,
    fused_cfg_velocity,
)

_TOY_VOCAB = 512


def _split_rows(val: jnp.ndarray, sizes: List[int], axis: int = 0) -> List[jnp.ndarray]:
    """Split a stacked batch back into per-request chunks along ``axis``."""
    out, off = [], 0
    for n in sizes:
        idx = (slice(None),) * axis + (slice(off, off + n),)
        out.append(val[idx])
        off += n
    return out


def _mesh_put(x: jnp.ndarray, mesh: Any, *spec: Any) -> jnp.ndarray:
    """Explicitly place an array on a submesh with the given PartitionSpec
    (device_put reshards committed single-device arrays, so stacked inputs
    built on the home device move onto the submesh in one transfer)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def _mesh_fn_cache(model_components: Dict[str, Any]) -> Dict[Any, Any]:
    """Per-components cache of jitted shard_map forwards, keyed by
    (mode, mesh).  Components are themselves cached per (model, patches,
    device set) by the backend, so entries live exactly as long as their
    placement does."""
    return model_components.setdefault("_sharded_fns", {})


_ML_STACK_CACHE_CAP = 16


def _cached_lora_stack(comps: Dict[str, Any], order: Tuple[str, ...],
                       adapters: Dict[str, Dict[str, Any]],
                       cache_key: str = "_ml_stacks",
                       field: str = "lora", build: Any = stack_loras) -> Any:
    """Grouped adapter stacks, cached per adapter ordering on the
    components dict (small LRU — a stack is a device-resident concat of
    the pool's decoded factors, rebuilt only when the tenant mix of a
    batch changes)."""
    cache = comps.setdefault(cache_key, OrderedDict())
    if order in cache:
        cache.move_to_end(order)
        return cache[order]
    stack = build([adapters[pid][field] for pid in order])
    cache[order] = stack
    while len(cache) > _ML_STACK_CACHE_CAP:
        cache.popitem(last=False)
    return stack


def _multilora_groups(batch_kwargs: List[Dict[str, Any]],
                      adapters: Dict[str, Dict[str, Any]],
                      field: str = "lora") -> Optional[Tuple]:
    """Per-request adapter grouping for a mixed batch: returns
    ``(order, per_request_idx)`` with ``order`` the distinct adapter ids
    (first-appearance order) and ``per_request_idx[i]`` the group of
    request i (-1 = unpatched), or ``None`` when the batch is outside the
    grouped form (a request with >1 patch, or no adapters at all)."""
    patch_ids = [tuple(p.model_id for p in kw.get("_patches") or [])
                 for kw in batch_kwargs]
    if any(len(ps) > 1 for ps in patch_ids):
        return None
    order: List[str] = []
    for ps in patch_ids:
        for pid in ps:
            if pid not in order and field in adapters.get(pid, {}):
                order.append(pid)
    if not order:
        return None
    g_of = {pid: g for g, pid in enumerate(order)}
    per_req = [g_of.get(ps[0], -1) if ps else -1 for ps in patch_ids]
    return tuple(order), per_req


# --------------------------------------------------------------------------
# Component models
# --------------------------------------------------------------------------

class LatentsGenerator(Model):
    trivial = True

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id="latents_generator")

    def setup_io(self) -> None:
        self.add_input("seed", int)
        self.add_output("latents", TensorType())

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        cfg = self.family.dit
        key = jax.random.PRNGKey(int(kw["seed"]))
        lat = jax.random.normal(
            key, (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)
        )
        return {"latents": lat}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        cfg = self.family.dit
        shape = (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)
        keys = jnp.stack(
            [jax.random.PRNGKey(int(kw["seed"])) for kw in batch_kwargs])
        lats = jax.vmap(lambda k: jax.random.normal(k, shape))(keys)
        return [{"latents": lats[i]} for i in range(len(batch_kwargs))]

    def cost(self) -> ModelCost:
        return ModelCost(1e6, 0, 1e6, self.family.latent_bytes(), max_batch=64)


class TextEncoder(Model):
    supports_multilora = True

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id=f"text_encoder:{family.name}")

    def setup_io(self) -> None:
        self.add_input("prompt", str)
        self.add_output("prompt_embeds", TensorType())

    def load(self, device: Any = None) -> Dict[str, Any]:
        cfg = self.family.dit
        params = quantize_text_params(init_text_encoder(
            jax.random.PRNGKey(stable_hash(self.model_id) % 2**31),
            _TOY_VOCAB, cfg.text_dim, n_layers=2, n_heads=4,
            max_len=cfg.text_tokens,
        ))
        apply = jax.jit(lambda p, ids: text_encoder_apply(p, ids, n_heads=4))
        apply_ml = jax.jit(
            lambda p, ids, stack, idx: text_encoder_apply(
                p, ids, n_heads=4, lora_stack=stack, lora_idx=idx))
        return {"params": params, "apply": apply, "apply_ml": apply_ml}

    def fold_patches(
        self,
        components: Dict[str, Any],
        patches: List[Model],
        patch_components: List[Dict[str, Any]],
    ) -> Dict[str, Any]:
        params = components["params"]
        for pc in patch_components:
            if "text_lora" in pc:
                params = fold_text_lora(params, pc["text_lora"])
        # quantize-on-fold: the backend's fold cache stores this copy, so
        # it carries the active REPRO_QUANT representation even when the
        # base components predate a mode flip
        return {**components, "params": quantize_text_params(params)}

    def execute_batch_multilora(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        adapters: Dict[str, Dict[str, Any]],
    ) -> Optional[List[Dict[str, Any]]]:
        groups = _multilora_groups(batch_kwargs, adapters, field="text_lora")
        apply_ml = model_components.get("apply_ml")
        if groups is None or apply_ml is None:
            return None
        order, per_req = groups
        stack = _cached_lora_stack(
            model_components, order, adapters, cache_key="_ml_text_stacks",
            field="text_lora", build=stack_text_loras)
        cfg = self.family.dit
        ids = tokenize_batch([kw["prompt"] for kw in batch_kwargs],
                             _TOY_VOCAB, cfg.text_tokens)
        idx = jnp.asarray(np.asarray(per_req, np.int32))
        emb = apply_ml(model_components["params"], ids, stack, idx)
        return [{"prompt_embeds": emb[i:i + 1]} for i in range(len(batch_kwargs))]

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        cfg = self.family.dit
        ids = tokenize(kw["prompt"], _TOY_VOCAB, cfg.text_tokens)
        emb = model_components["apply"](model_components["params"], ids)
        return {"prompt_embeds": emb}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        cfg = self.family.dit
        ids = tokenize_batch([kw["prompt"] for kw in batch_kwargs],
                             _TOY_VOCAB, cfg.text_tokens)
        emb = model_components["apply"](model_components["params"], ids)
        return [{"prompt_embeds": emb[i:i + 1]} for i in range(len(batch_kwargs))]

    def cost(self) -> ModelCost:
        f = self.family
        return ModelCost(
            flops_per_item=f.text_encode_flops(),
            param_bytes=f.text_encoder_bytes(),
            act_io_bytes=f.text_encoder_bytes(),      # memory-bound at b=1
            output_bytes=f.text_tokens * 4096 * 2.0,
            max_batch=32,
            # grouped multi-LoRA pricing: one target (last layer's wo),
            # two skinny matmuls per row, bf16 A/B factors per adapter
            lora_rank=8,
            lora_flops_per_rank=4.0 * f.text_tokens * 4096,
            lora_bytes_per_adapter=4.0 * 4096 * 8,
            quantizable=True,            # qdense projections (REPRO_QUANT)
        )


class DiffusionBackbone(Model):
    """One denoising step of the base diffusion model (CFG included).

    ``eager_controlnet=True`` declares the ControlNet residuals as an
    EAGER input (serializing ControlNet before the backbone) — the
    ablation baseline for deferred-fetch inter-node parallelism (§7.3).
    """

    scan_role = "backbone"
    supports_multilora = True

    def __init__(self, family: DiffusionFamily, eager_controlnet: bool = False) -> None:
        self.family = family
        self.eager_controlnet = eager_controlnet
        super().__init__(model_id=f"backbone:{family.name}")

    def setup_io(self) -> None:
        self.add_input("latents", TensorType())
        self.add_input("prompt_embeds", TensorType())
        self.add_input("t", float)
        self.add_input("controlnet_residuals", TensorType(),
                       deferred=not getattr(self, "eager_controlnet", False))
        self.add_input("guidance", float)
        self.add_output("velocity", TensorType())

    def load(self, device: Any = None) -> Dict[str, Any]:
        cfg = self.family.dit
        params = quantize_mmdit_params(init_mmdit(
            jax.random.PRNGKey(stable_hash(self.model_id) % 2**31), cfg))
        apply = jax.jit(
            lambda p, lat, t, emb, res: mmdit_apply(p, cfg, lat, t, emb, res)
        )
        uses_cfg = self.family.uses_cfg

        def _forward(p, lat, t, emb, res, guidance):
            # one-pass CFG fused INSIDE the jit: cond+null stacked on the
            # batch axis, so the whole step is a single host->device call
            if uses_cfg:
                return fused_cfg_velocity(
                    lambda pp, l, tt, e, r: mmdit_apply(pp, cfg, l, tt, e, r),
                    p, lat, t, emb, guidance, res)
            return mmdit_apply(p, cfg, lat, t, emb, res)

        def _forward_ml(p, lat, t, emb, res, guidance, stack, idx):
            # grouped multi-adapter forward: per-row LoRAs against the
            # SHARED base params (no fold); CFG duplicates the adapter
            # index vector alongside the latent rows
            if uses_cfg:
                idx2 = jnp.concatenate([idx, idx])
                return fused_cfg_velocity(
                    lambda pp, l, tt, e, r: mmdit_apply(
                        pp, cfg, l, tt, e, r, lora_stack=stack, lora_idx=idx2),
                    p, lat, t, emb, guidance, res)
            return mmdit_apply(p, cfg, lat, t, emb, res,
                               lora_stack=stack, lora_idx=idx)

        return {"params": params, "apply": apply,
                "forward": jax.jit(_forward),
                "forward_ml": jax.jit(_forward_ml), "cfg": cfg}

    def fold_patches(
        self,
        components: Dict[str, Any],
        patches: List[Model],
        patch_components: List[Dict[str, Any]],
    ) -> Dict[str, Any]:
        """LoRA fold, done ONCE per (model, patch set) by the backend.

        Quantize-on-fold: the folded copy the backend caches carries the
        active ``REPRO_QUANT`` representation (fold dequantizes the
        targets, applies the delta in f32, requantizes)."""
        params = components["params"]
        for pc in patch_components:
            params = fold_lora(params, pc["lora"])
        return {**components, "params": quantize_mmdit_params(params)}

    def _velocity(
        self,
        model_components: Dict[str, Any],
        params: Dict[str, Any],
        lat: jnp.ndarray,
        t: jnp.ndarray,
        emb: jnp.ndarray,
        res: jnp.ndarray,
        guidance: Any,
    ) -> jnp.ndarray:
        forward = model_components.get("forward")
        g = jnp.asarray(np.broadcast_to(
            np.asarray(guidance, np.float32), (lat.shape[0],)))
        if forward is not None:
            return forward(params, lat, t, emb, res, g)
        # components loaded elsewhere: python-side one-pass CFG fallback
        apply = model_components["apply"]
        if self.family.uses_cfg:
            return fused_cfg_velocity(apply, params, lat, t, emb, g, res)
        return apply(params, lat, t, emb, res)

    def _materialize_residuals(self, cfg: DiTConfig, kw: Dict[str, Any],
                               lat: jnp.ndarray) -> jnp.ndarray:
        res = kw.get("controlnet_residuals")
        if res is None:
            res = jnp.zeros(
                (cfg.n_layers, lat.shape[0], cfg.image_tokens, cfg.d_model),
                cfg.dtype,
            )
        return res

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        cfg: DiTConfig = model_components["cfg"]
        params = model_components["params"]
        for patch in kw.get("_patches", []) or []:
            # legacy direct-call path; the serving runtime folds via the
            # backend's (model_id, patch_ids) cache instead
            lora_params = patch.load()["lora"]
            params = fold_lora(params, lora_params)
        lat = kw["latents"]
        emb = kw["prompt_embeds"]
        t = jnp.full((lat.shape[0],), float(kw["t"]))
        res = self._materialize_residuals(cfg, kw, lat)
        v = self._velocity(model_components, params, lat, t, emb, res,
                           float(kw.get("guidance", 4.5)))
        return {"velocity": v}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        """Stacked cross-request forward.  Batch axis is axis 0 for
        latents/embeddings but axis 1 for the layer-major ControlNet
        residual stacks; timesteps and guidance become per-item vectors."""
        cfg: DiTConfig = model_components["cfg"]
        params = model_components["params"]
        patch_sets = [tuple(p.model_id for p in kw.get("_patches", []) or [])
                      for kw in batch_kwargs]
        if any(ps != patch_sets[0] for ps in patch_sets[1:]):
            # mixed patch sets can't share one folded parameter set
            # (the serving runtime never batches them — batch_key includes
            # effective_patches — but direct callers might)
            return self._execute_sequential(model_components, batch_kwargs)
        for patch in batch_kwargs[0].get("_patches", []) or []:
            params = fold_lora(params, patch.load()["lora"])
        stacked = self._stack_batch(cfg, batch_kwargs)
        if stacked is None:
            return self._execute_sequential(model_components, batch_kwargs)
        lat, emb, t, res, guidance, sizes = stacked
        v = self._velocity(model_components, params, lat, t, emb, res, guidance)
        return [{"velocity": chunk} for chunk in _split_rows(v, sizes)]

    def execute_batch_multilora(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        adapters: Dict[str, Dict[str, Any]],
    ) -> Optional[List[Dict[str, Any]]]:
        """One stacked forward for a batch MIXING adapters: per-row grouped
        LoRA against the shared base params (the unfolded serving mode) —
        no per-tenant fold, no parameter mutation."""
        groups = _multilora_groups(batch_kwargs, adapters)
        forward_ml = model_components.get("forward_ml")
        if groups is None or forward_ml is None:
            return None
        cfg: DiTConfig = model_components["cfg"]
        stacked = self._stack_batch(cfg, batch_kwargs)
        if stacked is None:
            return None
        order, per_req = groups
        lat, emb, t, res, guidance, sizes = stacked
        stack = _cached_lora_stack(model_components, order, adapters)
        idx = jnp.asarray(np.repeat(np.asarray(per_req, np.int32), sizes))
        g = jnp.asarray(np.broadcast_to(
            np.asarray(guidance, np.float32), (lat.shape[0],)))
        v = forward_ml(model_components["params"], lat, t, emb, res, g,
                       stack, idx)
        return [{"velocity": chunk} for chunk in _split_rows(v, sizes)]

    def _stack_batch(
        self, cfg: DiTConfig, batch_kwargs: List[Dict[str, Any]]
    ) -> Optional[Tuple]:
        """Stack a cross-request batch: (lat, emb, t, res, guidance, sizes),
        or None when shapes disagree and stacking would be unsound."""
        lats = [kw["latents"] for kw in batch_kwargs]
        embs = [kw["prompt_embeds"] for kw in batch_kwargs]
        if (any(l.shape[1:] != lats[0].shape[1:] for l in lats[1:])
                or any(e.shape[1:] != embs[0].shape[1:] for e in embs[1:])):
            return None
        sizes = [int(l.shape[0]) for l in lats]
        lat = jnp.concatenate(lats, axis=0)
        emb = jnp.concatenate(embs, axis=0)
        # per-item scalars become [B] vectors; built host-side in ONE
        # transfer instead of B tiny device ops
        t = jnp.asarray(np.repeat(
            np.asarray([float(kw["t"]) for kw in batch_kwargs], np.float32),
            sizes))
        res = jnp.concatenate([
            self._materialize_residuals(cfg, kw, l)
            for kw, l in zip(batch_kwargs, lats)
        ], axis=1)
        guidance = np.repeat(
            np.asarray([float(kw.get("guidance", 4.5))
                        for kw in batch_kwargs], np.float32), sizes)
        return lat, emb, t, res, guidance, sizes

    def execute_batch_sharded(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        mesh: Any,
    ) -> Optional[List[Dict[str, Any]]]:
        """Stacked forward as one SPMD program over the k-device submesh.

        Two composition modes, chosen by shape:

        * **latent/CFG-branch data parallelism** — the CFG pair is folded
          onto the batch axis host-side (cond rows then null rows) and the
          rows are sharded across the mesh: at k=2/B=1 the conditional and
          unconditional branches run on different devices (the paper's
          latent parallelism), at larger B whole requests spread out.
          Per-item guidance stays a [B] vector applied after the gather,
          so mixed guidance scales remain fusable.
        * **sequence sharding** — when the row count does not divide by k
          (e.g. one CFG pair on a k=4 submesh), the image tokens shard
          instead (``mmdit_apply_seq_sharded``), with per-layer K/V
          all-gathers keeping joint attention exact.

        Returns None when neither mode fits (the backend falls back to the
        single-device stacked forward).
        """
        import jax

        if any(kw.get("_patches") for kw in batch_kwargs):
            return None      # backend lifts uniform patches before us
        cfg: DiTConfig = model_components["cfg"]
        stacked = self._stack_batch(cfg, batch_kwargs)
        if stacked is None:
            return None
        lat, emb, t, res, guidance, sizes = stacked
        params = model_components["params"]
        uses_cfg = self.family.uses_cfg
        b = int(lat.shape[0])
        if uses_cfg:     # fold CFG onto the batch axis before sharding
            lat = jnp.concatenate([lat, lat], axis=0)
            t = jnp.concatenate([t, t], axis=0)
            emb = jnp.concatenate([emb, jnp.zeros_like(emb)], axis=0)
            res = jnp.concatenate([res, res], axis=1)
        k = mesh.size
        axis = mesh.axis_names[0]
        cache = _mesh_fn_cache(model_components)
        if int(lat.shape[0]) % k == 0:
            key = ("dp", mesh)
            if key not in cache:
                cache[key] = jax.jit(shard_map_compat(
                    lambda p, l, tt, e, r: mmdit_apply(p, cfg, l, tt, e, r),
                    mesh=mesh,
                    in_specs=(P(), P(axis), P(axis), P(axis), P(None, axis)),
                    out_specs=P(axis),
                ))
            v2 = cache[key](params,
                            _mesh_put(lat, mesh, axis),
                            _mesh_put(t, mesh, axis),
                            _mesh_put(emb, mesh, axis),
                            _mesh_put(res, mesh, None, axis))
        elif seq_shard_divisor(cfg, k):
            key = ("seq", mesh)
            if key not in cache:
                cache[key] = jax.jit(
                    lambda p, l, tt, e, r: mmdit_apply_seq_sharded(
                        p, cfg, l, tt, e, r, mesh))
            v2 = cache[key](params,
                            _mesh_put(lat, mesh, None, axis),
                            _mesh_put(t, mesh),
                            _mesh_put(emb, mesh),
                            _mesh_put(res, mesh, None, None, axis))
        else:
            return None
        if uses_cfg:
            v_c, v_u = v2[:b], v2[b:]
            g = jnp.asarray(guidance, v2.dtype)
            g = g.reshape((b,) + (1,) * (v2.ndim - 1))
            v = cfg_combine(v_u, v_c, g)
        else:
            v = v2
        return [{"velocity": chunk} for chunk in _split_rows(v, sizes)]

    def cost(self) -> ModelCost:
        f = self.family
        g = f.published
        tokens = f.image_tokens + f.text_tokens
        return ModelCost(
            flops_per_item=f.backbone_step_flops(),
            param_bytes=f.backbone_bytes(),
            act_io_bytes=12.0 * g.n_layers * tokens * g.d_model * 2.0,
            output_bytes=f.image_tokens * 16 * 2.0,
            # k_max profiled for the sharded plane: 2x from the CFG/latent
            # branch split, 2x more from batch-row or sequence sharding
            max_parallelism=4,
            max_batch=8,
            calls_per_request=f.denoise_steps,
            # grouped multi-LoRA pricing (§5.1 extended): 4 img-stream
            # targets × n_layers, two skinny matmuls per row per rank;
            # per-adapter HBM traffic is the bf16 A/B factor stream
            lora_rank=8,
            lora_flops_per_rank=16.0 * g.n_layers * f.image_tokens * g.d_model,
            lora_bytes_per_adapter=16.0 * g.n_layers * g.d_model * 8,
            # stream projections quantize (REPRO_QUANT): the roofline
            # prices int8 forwards at the doubled MXU issue rate and the
            # halved weight stream
            quantizable=True,
        )

    def build_segment(self, controlnets: List["ControlNet"],
                      n_steps: int) -> "DenoiseSegment":
        """Factory the :class:`~repro.core.passes.SegmentFusionPass` calls
        to materialize a fused multi-step op for a recognized chain."""
        return DenoiseSegment(self, controlnets, n_steps)


class ControlNet(Model):
    scan_role = "controlnet"

    def __init__(self, family: DiffusionFamily, variant: int = 1) -> None:
        self.family = family
        self.variant = variant
        super().__init__(model_id=f"controlnet{variant}:{family.name}")

    def setup_io(self) -> None:
        self.add_input("latents", TensorType())
        self.add_input("cond_latents", TensorType())
        self.add_input("prompt_embeds", TensorType())
        self.add_input("t", float)
        self.add_output("controlnet_residuals", TensorType())

    def load(self, device: Any = None) -> Dict[str, Any]:
        cfg = self.family.dit
        params = quantize_mmdit_params(init_controlnet(
            jax.random.PRNGKey(stable_hash(self.model_id) % 2**31), cfg
        ))
        apply = jax.jit(
            lambda p, lat, cond, t, emb: controlnet_apply(p, cfg, lat, cond, t, emb)
        )
        return {"params": params, "apply": apply}

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        lat = kw["latents"]
        t = jnp.full((lat.shape[0],), float(kw["t"]))
        res = model_components["apply"](
            model_components["params"], lat, kw["cond_latents"], t,
            kw["prompt_embeds"],
        )
        return {"controlnet_residuals": res}

    @staticmethod
    def _stack_batch(batch_kwargs: List[Dict[str, Any]]) -> Optional[Tuple]:
        """Stack a cross-request batch: (lat, cond, emb, t, sizes), or
        None when latent shapes disagree and stacking would be unsound."""
        lats = [kw["latents"] for kw in batch_kwargs]
        if any(l.shape[1:] != lats[0].shape[1:] for l in lats[1:]):
            return None
        sizes = [int(l.shape[0]) for l in lats]
        lat = jnp.concatenate(lats, axis=0)
        cond = jnp.concatenate([kw["cond_latents"] for kw in batch_kwargs], axis=0)
        emb = jnp.concatenate([kw["prompt_embeds"] for kw in batch_kwargs], axis=0)
        t = jnp.asarray(np.repeat(
            np.asarray([float(kw["t"]) for kw in batch_kwargs], np.float32),
            sizes))
        return lat, cond, emb, t, sizes

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        stacked = self._stack_batch(batch_kwargs)
        if stacked is None:
            return self._execute_sequential(model_components, batch_kwargs)
        lat, cond, emb, t, sizes = stacked
        res = model_components["apply"](
            model_components["params"], lat, cond, t, emb)
        # residuals are layer-major [L, B, Ti, d]: batch axis is axis 1
        return [{"controlnet_residuals": chunk}
                for chunk in _split_rows(res, sizes, axis=1)]

    def execute_batch_sharded(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        mesh: Any,
    ) -> Optional[List[Dict[str, Any]]]:
        """Batch-axis data parallelism for the ControlNet branch: requests
        shard across the submesh; the layer-major residual stack comes back
        sharded on its batch axis (axis 1)."""
        import jax

        if any(kw.get("_patches") for kw in batch_kwargs):
            return None
        stacked = self._stack_batch(batch_kwargs)
        if stacked is None:
            return None
        lat, cond, emb, t, sizes = stacked
        if sum(sizes) % mesh.size:
            return None
        cfg = self.family.dit
        axis = mesh.axis_names[0]
        cache = _mesh_fn_cache(model_components)
        key = ("cn", mesh)
        if key not in cache:
            cache[key] = jax.jit(shard_map_compat(
                lambda p, l, cnd, tt, e: controlnet_apply(p, cfg, l, cnd, tt, e),
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
                out_specs=P(None, axis),
            ))
        res = cache[key](model_components["params"],
                         _mesh_put(lat, mesh, axis),
                         _mesh_put(cond, mesh, axis),
                         _mesh_put(t, mesh, axis),
                         _mesh_put(emb, mesh, axis))
        return [{"controlnet_residuals": chunk}
                for chunk in _split_rows(res, sizes, axis=1)]

    def cost(self) -> ModelCost:
        f = self.family
        g = f.published
        return ModelCost(
            flops_per_item=f.controlnet_step_flops(),
            param_bytes=f.controlnet_bytes(),
            act_io_bytes=6.0 * g.n_layers * (f.image_tokens + f.text_tokens)
            * g.d_model,
            output_bytes=f.controlnet_residual_bytes(),
            max_parallelism=2,           # batch-axis data parallelism
            max_batch=8,
            calls_per_request=f.denoise_steps,
            quantizable=True,            # same stream projections as MMDiT
        )


class VAEDecode(Model):
    # decode of batch N may overlap the next batch's denoise segment on
    # the same executor (REPRO_OVERLAP): stateless, no patches, and its
    # VPU/memory-bound conv stack interleaves under the MXU-bound
    # backbone forward
    overlappable = True

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id=f"vae:{family.name}")

    def setup_io(self) -> None:
        self.add_input("latents", TensorType())
        self.add_output("image", Image)

    def load(self, device: Any = None) -> Dict[str, Any]:
        cfg = self.family.dit
        params = init_vae(
            jax.random.PRNGKey(stable_hash(f"vae:{self.family.name}") % 2**31),
            latent_channels=cfg.latent_channels,
        )
        return {
            "params": params,
            "decode": jax.jit(vae_decode),
            "encode": jax.jit(vae_encode),
        }

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        img = model_components["decode"](model_components["params"], kw["latents"])
        return {"image": img}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        lats = [kw["latents"] for kw in batch_kwargs]
        if any(l.shape[1:] != lats[0].shape[1:] for l in lats[1:]):
            return self._execute_sequential(model_components, batch_kwargs)
        sizes = [int(l.shape[0]) for l in lats]
        img = model_components["decode"](
            model_components["params"], jnp.concatenate(lats, axis=0))
        return [{"image": chunk} for chunk in _split_rows(img, sizes)]

    def execute_batch_sharded(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        mesh: Any,
    ) -> Optional[List[Dict[str, Any]]]:
        """Replicated-weight parallel decode: the VAE params live on every
        submesh device, latent rows shard across them."""
        import jax

        lats = [kw["latents"] for kw in batch_kwargs]
        if any(l.shape[1:] != lats[0].shape[1:] for l in lats[1:]):
            return None
        sizes = [int(l.shape[0]) for l in lats]
        if sum(sizes) % mesh.size:
            return None
        axis = mesh.axis_names[0]
        # decode/encode share one components dict (same model_id), so the
        # fn cache keys carry the op kind
        cache = _mesh_fn_cache(model_components)
        key = ("vae_dec", mesh)
        if key not in cache:
            cache[key] = jax.jit(shard_map_compat(
                lambda p, l: vae_decode(p, l), mesh=mesh,
                in_specs=(P(), P(axis)), out_specs=P(axis)))
        img = cache[key](model_components["params"],
                          _mesh_put(jnp.concatenate(lats, axis=0), mesh, axis))
        return [{"image": chunk} for chunk in _split_rows(img, sizes)]

    def cost(self) -> ModelCost:
        f = self.family
        return ModelCost(
            flops_per_item=f.vae_decode_flops(),
            param_bytes=f.vae_bytes(),
            act_io_bytes=f.image_tokens * 64 * 48.0,
            output_bytes=f.image_tokens * 64 * 3 * 1.0,   # uint8 pixels
            max_parallelism=2,           # replicated-weight parallel decode
            max_batch=16,
        )


class VAEEncode(Model):
    """Reference-image encoder; shares the VAE weights (same model_id)."""

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id=f"vae:{family.name}")

    def setup_io(self) -> None:
        self.add_input("image", Image)
        self.add_output("cond_latents", TensorType())

    def load(self, device: Any = None) -> Dict[str, Any]:
        return VAEDecode(self.family).load(device)

    def _as_array(self, img: Any) -> jnp.ndarray:
        if not hasattr(img, "shape"):   # toy stand-in for a PIL image
            cfg = self.family.dit
            img = jnp.zeros((1, cfg.latent_size * 8, cfg.latent_size * 8, 3))
        return img

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        img = self._as_array(kw["image"])
        lat = model_components["encode"](model_components["params"], img)
        return {"cond_latents": lat}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        imgs = [self._as_array(kw["image"]) for kw in batch_kwargs]
        if any(i.shape[1:] != imgs[0].shape[1:] for i in imgs[1:]):
            return self._execute_sequential(model_components, batch_kwargs)
        sizes = [int(i.shape[0]) for i in imgs]
        lat = model_components["encode"](
            model_components["params"], jnp.concatenate(imgs, axis=0))
        return [{"cond_latents": chunk} for chunk in _split_rows(lat, sizes)]

    def execute_batch_sharded(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        mesh: Any,
    ) -> Optional[List[Dict[str, Any]]]:
        """Replicated-weight parallel encode (mirror of VAEDecode)."""
        import jax

        imgs = [self._as_array(kw["image"]) for kw in batch_kwargs]
        if any(i.shape[1:] != imgs[0].shape[1:] for i in imgs[1:]):
            return None
        sizes = [int(i.shape[0]) for i in imgs]
        if sum(sizes) % mesh.size:
            return None
        axis = mesh.axis_names[0]
        cache = _mesh_fn_cache(model_components)
        key = ("vae_enc", mesh)
        if key not in cache:
            cache[key] = jax.jit(shard_map_compat(
                lambda p, i: vae_encode(p, i), mesh=mesh,
                in_specs=(P(), P(axis)), out_specs=P(axis)))
        lat = cache[key](model_components["params"],
                          _mesh_put(jnp.concatenate(imgs, axis=0), mesh, axis))
        return [{"cond_latents": chunk} for chunk in _split_rows(lat, sizes)]

    def cost(self) -> ModelCost:
        c = VAEDecode(self.family).cost()
        return ModelCost(c.flops_per_item, c.param_bytes, c.act_io_bytes,
                         self.family.latent_bytes(),
                         max_parallelism=c.max_parallelism, max_batch=16)


class DenoiseStep(Model):
    """Euler scheduler step — trivial arithmetic, runs inline."""

    trivial = True
    scan_role = "denoise"

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id="denoise_step")

    def setup_io(self) -> None:
        self.add_input("velocity", TensorType())
        self.add_input("latents", TensorType())
        self.add_input("t_cur", float)
        self.add_input("t_next", float)
        self.add_output("latents", TensorType())

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        lat = denoise_step_jit(
            kw["latents"], kw["velocity"],
            jnp.asarray(kw["t_cur"]), jnp.asarray(kw["t_next"]),
        )
        return {"latents": lat}

    def cost(self) -> ModelCost:
        return ModelCost(1e6, 0, 1e6, self.family.latent_bytes(), max_batch=64)


class ResidualCombine(Model):
    """Sum residual stacks from multiple ControlNets — trivial, inline."""

    trivial = True
    scan_role = "combine"

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        super().__init__(model_id="residual_combine")

    def setup_io(self) -> None:
        self.add_input("a", TensorType())
        self.add_input("b", TensorType())
        self.add_output("controlnet_residuals", TensorType())

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        return {"controlnet_residuals": kw["a"] + kw["b"]}

    def cost(self) -> ModelCost:
        return ModelCost(1e6, 0, 1e6,
                         self.family.controlnet_residual_bytes(), max_batch=64)


class DenoiseSegment(Model):
    """A fused run of S consecutive denoising steps — ONE device dispatch.

    Built by :class:`~repro.core.passes.SegmentFusionPass` from a
    recognized ``ControlNet* → ResidualCombine* → DiffusionBackbone →
    DenoiseStep`` chain: the whole chunk executes as a single jitted
    ``jax.lax.scan`` whose body mirrors the unfused per-step arithmetic
    exactly (ControlNet residual fan-in, one-pass fused CFG, Euler
    update), so a segment of S steps costs one host→device call instead
    of S×(2-4) graph-node dispatches.

    The step schedule travels in the NODE inputs (``t_mid``/``t_cur``/
    ``t_next`` tuples + ``guidance``), not in the op: two workflows with
    different step counts share one ``model_id`` (and therefore one set
    of loaded components), and cross-request batches may mix schedules.
    The runtime executes segments in load-adaptive chunks via the
    reserved ``_seg_start`` / ``_seg_steps`` kwargs; LoRA patches fold
    into the backbone params once per placement (at chunk boundaries —
    Katz semantics for adapters that arrive mid-request).
    """

    is_segment = True
    supports_multilora = True

    def __init__(self, backbone: DiffusionBackbone,
                 controlnets: Sequence[ControlNet], n_steps: int) -> None:
        self.backbone = backbone
        self.cns = list(controlnets)
        self.family = backbone.family
        self.n_steps = int(n_steps)
        mid = "segment:" + backbone.model_id + "".join(
            f"+{cn.model_id}" for cn in self.cns)
        super().__init__(model_id=mid)

    def setup_io(self) -> None:
        self.add_input("latents", TensorType())
        self.add_input("prompt_embeds", TensorType())
        if self.cns:
            self.add_input("cond_latents", TensorType())
        # untyped literal ports: the per-step schedule, captured by the
        # fusion pass from the unfused chain's node literals
        self.add_input("t_mid", None)
        self.add_input("t_cur", None)
        self.add_input("t_next", None)
        self.add_input("guidance", None)
        self.add_output("latents", TensorType())

    # ------------------------------------------------------------ loading
    @property
    def patches(self) -> List[Model]:
        # the segment IS the backbone for patching purposes: AsyncLoRAPass
        # and the scheduler's effective-patch tracking see through it
        return self.backbone.patches

    def load(self, device: Any = None) -> Dict[str, Any]:
        comps: Dict[str, Any] = {
            "backbone": self.backbone.load(device),
            "cns": [cn.load(device) for cn in self.cns],
            "cfg": self.family.dit,
            # donation is baked into the jit at load time (REPRO_DONATE);
            # execute() consults this marker for the copy-on-first-chunk
            # guard
            "donate": donate_buffers_enabled(),
        }
        comps["scan"] = self._make_scan()
        comps["scan_ml"] = self._make_scan(multilora=True)
        return comps

    def fold_patches(
        self,
        components: Dict[str, Any],
        patches: List[Model],
        patch_components: List[Dict[str, Any]],
    ) -> Dict[str, Any]:
        folded = self.backbone.fold_patches(
            components["backbone"], patches, patch_components)
        return {**components, "backbone": folded}

    # ----------------------------------------------------------- the scan
    def _make_scan(self, multilora: bool = False) -> Any:
        """One jitted scan over the chunk.  The body is the UNFUSED
        per-step arithmetic verbatim (same residual fan-in order, same
        fused-CFG call, same Euler update) so fused output == unfused
        output bit for bit; jit recompiles per distinct (S, B) shape.

        ``multilora=True`` builds the grouped multi-adapter variant: the
        scan takes the stacked LoRA factors plus a per-row adapter index,
        and every step applies each row's adapter against the shared base
        params (no fold) — cross-tenant requests share one segment."""
        cfg = self.family.dit
        uses_cfg = self.family.uses_cfg
        n_cns = len(self.cns)

        def run(pb, pcns, lat, emb, cond, t_mid, t_cur, t_next, guidance,
                stack=None, idx=None):
            # lat [B,H,W,C]; emb [B,Tc,D]; t_* [S,B]; guidance [B]
            idx2 = (jnp.concatenate([idx, idx])
                    if multilora and uses_cfg else idx)

            def bb_apply(p, l, tt, e, r):
                if multilora:
                    return mmdit_apply(p, cfg, l, tt, e, r,
                                       lora_stack=stack,
                                       lora_idx=idx2 if uses_cfg else idx)
                return mmdit_apply(p, cfg, l, tt, e, r)

            def body(lat, xs):
                t, tc, tn = xs
                if n_cns:
                    res = None
                    for pcn in pcns:
                        r = controlnet_apply(pcn, cfg, lat, cond, t, emb)
                        res = r if res is None else res + r
                else:
                    res = jnp.zeros(
                        (cfg.n_layers, lat.shape[0], cfg.image_tokens,
                         cfg.d_model), cfg.dtype)
                if uses_cfg:
                    v = fused_cfg_velocity(
                        bb_apply, pb, lat, t, emb, guidance, res)
                else:
                    v = bb_apply(pb, lat, t, emb, res)
                dt = (tn - tc).astype(lat.dtype)
                dt = dt.reshape((lat.shape[0],) + (1,) * (lat.ndim - 1))
                return lat + dt * v, None

            lat, _ = jax.lax.scan(body, lat, (t_mid, t_cur, t_next))
            return lat

        if donate_buffers_enabled():
            # donate the latent carry (positional arg 2): XLA aliases the
            # chunk's input latents to its output, so segment chunks
            # update the buffer in place across dispatches
            return jax.jit(run, donate_argnums=(2,))
        return jax.jit(run)

    # ---------------------------------------------------------- execution
    @staticmethod
    def _chunk_of(kw: Dict[str, Any]) -> Tuple[int, int]:
        """(start, steps) of the chunk this call covers."""
        total = len(kw["t_mid"])
        start = int(kw.get("_seg_start", 0) or 0)
        steps = kw.get("_seg_steps")
        steps = total - start if steps is None else int(steps)
        return start, max(0, min(steps, total - start))

    def _step_arrays(self, batch_kwargs: List[Dict[str, Any]],
                     sizes: List[int], steps: int) -> Tuple:
        """Stack per-item schedule slices into [S, B_rows] columns plus a
        per-row [B_rows] guidance vector — built host-side in one
        transfer, mirroring the unfused stacked forward."""
        cols = {"t_mid": [], "t_cur": [], "t_next": []}
        gs = []
        for kw, n in zip(batch_kwargs, sizes):
            start, _ = self._chunk_of(kw)
            for name in cols:
                sl = np.asarray(kw[name][start:start + steps], np.float32)
                cols[name].append(np.repeat(sl[:, None], n, axis=1))
            g = kw.get("guidance")
            gs.append(np.repeat(np.float32(4.5 if g is None else float(g)), n))
        return (jnp.asarray(np.concatenate(cols["t_mid"], axis=1)),
                jnp.asarray(np.concatenate(cols["t_cur"], axis=1)),
                jnp.asarray(np.concatenate(cols["t_next"], axis=1)),
                jnp.asarray(np.concatenate(gs)))

    def _stack_segment(self, batch_kwargs: List[Dict[str, Any]]) -> Optional[Tuple]:
        lats = [kw["latents"] for kw in batch_kwargs]
        embs = [kw["prompt_embeds"] for kw in batch_kwargs]
        if (any(l.shape[1:] != lats[0].shape[1:] for l in lats[1:])
                or any(e.shape[1:] != embs[0].shape[1:] for e in embs[1:])):
            return None
        chunks = [self._chunk_of(kw) for kw in batch_kwargs]
        steps = chunks[0][1]
        if any(c[1] != steps for c in chunks[1:]) or steps <= 0:
            return None
        sizes = [int(l.shape[0]) for l in lats]
        lat = jnp.concatenate(lats, axis=0)
        emb = jnp.concatenate(embs, axis=0)
        cond = jnp.zeros((0,))
        if self.cns:
            conds = [kw["cond_latents"] for kw in batch_kwargs]
            if any(c.shape[1:] != conds[0].shape[1:] for c in conds[1:]):
                return None
            cond = jnp.concatenate(conds, axis=0)
        t_mid, t_cur, t_next, guidance = self._step_arrays(
            batch_kwargs, sizes, steps)
        return lat, emb, cond, t_mid, t_cur, t_next, guidance, sizes

    def _params(self, comps: Dict[str, Any]) -> Tuple:
        return (comps["backbone"]["params"],
                tuple(c["params"] for c in comps["cns"]))

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        params = model_components["backbone"]["params"]
        for patch in kw.pop("_patches", []) or []:
            params = fold_lora(params, patch.load()["lora"])
        start, steps = self._chunk_of(kw)
        if steps <= 0:
            return {"latents": kw["latents"]}
        lat = kw["latents"]
        if model_components.get("donate") and start == 0:
            # never donate the datastore's buffer: the first chunk's
            # latents are an engine-held value other consumers (and
            # recovery) may still read — donate a private copy instead.
            # Later chunks receive the segment-owned carry, which this
            # scan's output replaces, so those donate in place.
            lat = jnp.copy(lat)
        b = int(lat.shape[0])
        t_mid, t_cur, t_next, guidance = self._step_arrays([kw], [b], steps)
        cond = kw.get("cond_latents") if self.cns else jnp.zeros((0,))
        out = model_components["scan"](
            params, tuple(c["params"] for c in model_components["cns"]),
            lat, kw["prompt_embeds"], cond, t_mid, t_cur, t_next, guidance)
        return {"latents": out}

    def execute_batch(
        self, model_components: Dict[str, Any], batch_kwargs: List[Dict[str, Any]]
    ) -> List[Dict[str, Any]]:
        if len(batch_kwargs) == 1:
            return [self.execute(model_components, **dict(batch_kwargs[0]))]
        patch_sets = [tuple(p.model_id for p in kw.get("_patches", []) or [])
                      for kw in batch_kwargs]
        if any(ps != patch_sets[0] for ps in patch_sets[1:]):
            return self._execute_sequential(model_components, batch_kwargs)
        params = model_components["backbone"]["params"]
        for patch in batch_kwargs[0].get("_patches", []) or []:
            params = fold_lora(params, patch.load()["lora"])
        stacked = self._stack_segment(batch_kwargs)
        if stacked is None:
            return self._execute_sequential(model_components, batch_kwargs)
        lat, emb, cond, t_mid, t_cur, t_next, guidance, sizes = stacked
        out = model_components["scan"](
            params, tuple(c["params"] for c in model_components["cns"]),
            lat, emb, cond, t_mid, t_cur, t_next, guidance)
        return [{"latents": chunk} for chunk in _split_rows(out, sizes)]

    def execute_batch_multilora(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        adapters: Dict[str, Dict[str, Any]],
    ) -> Optional[List[Dict[str, Any]]]:
        """The whole chunk as one grouped multi-adapter scan: cross-tenant
        requests share the segment; each step applies per-row adapters."""
        groups = _multilora_groups(batch_kwargs, adapters)
        scan_ml = model_components.get("scan_ml")
        if groups is None or scan_ml is None:
            return None
        stacked = self._stack_segment(batch_kwargs)
        if stacked is None:
            return None
        order, per_req = groups
        lat, emb, cond, t_mid, t_cur, t_next, guidance, sizes = stacked
        stack = _cached_lora_stack(model_components, order, adapters)
        idx = jnp.asarray(np.repeat(np.asarray(per_req, np.int32), sizes))
        out = scan_ml(
            model_components["backbone"]["params"],
            tuple(c["params"] for c in model_components["cns"]),
            lat, emb, cond, t_mid, t_cur, t_next, guidance, stack, idx)
        return [{"latents": chunk} for chunk in _split_rows(out, sizes)]

    def clamp_parallelism(self, batch_size: int, k: int) -> int:
        """Largest k' ≤ k with a real sharded mode: the folded CFG rows
        divide k' (row DP), or the patch grid divides k' (sequence
        sharding inside the scan)."""
        rows = batch_size * (2 if self.family.uses_cfg else 1)
        for j in range(k, 0, -1):
            if rows % j == 0 or seq_shard_divisor(self.family.dit, j):
                return j
        return 1

    def execute_batch_sharded(
        self,
        model_components: Dict[str, Any],
        batch_kwargs: List[Dict[str, Any]],
        mesh: Any,
    ) -> Optional[List[Dict[str, Any]]]:
        """The whole chunk as one SPMD scan over the k-device submesh:
        the CFG pair folds onto the batch axis INSIDE the scan body and
        rows shard across the mesh (latent/CFG-branch data parallelism);
        ControlNet branches run on the same folded rows.  Declines (None)
        when the folded row count does not divide k — the backend then
        falls back to the single-device scan."""
        import jax

        if any(kw.get("_patches") for kw in batch_kwargs):
            return None      # backend lifts uniform patches before us
        stacked = self._stack_segment(batch_kwargs)
        if stacked is None:
            return None
        lat, emb, cond, t_mid, t_cur, t_next, guidance, sizes = stacked
        rows = int(lat.shape[0]) * (2 if self.family.uses_cfg else 1)
        k = mesh.size
        if rows % k and not seq_shard_divisor(self.family.dit, k):
            return None      # neither row-DP nor sequence sharding fits
        cache = _mesh_fn_cache(model_components)
        key = ("segment", mesh)
        if key not in cache:
            cache[key] = jax.jit(self._make_sharded_scan(mesh))
        # inputs may arrive committed to a previous placement (the home
        # device, or a different submesh after a recovery re-dispatch of
        # a chunked segment); replicate them onto THIS submesh so they
        # agree with the replicated params
        out = cache[key](*self._params(model_components),
                         _mesh_put(lat, mesh), _mesh_put(emb, mesh),
                         _mesh_put(cond, mesh), _mesh_put(t_mid, mesh),
                         _mesh_put(t_cur, mesh), _mesh_put(t_next, mesh),
                         _mesh_put(guidance, mesh))
        return [{"latents": chunk} for chunk in _split_rows(out, sizes)]

    def _make_sharded_scan(self, mesh: Any) -> Any:
        cfg = self.family.dit
        uses_cfg = self.family.uses_cfg
        n_cns = len(self.cns)
        k = mesh.size
        axis = mesh.axis_names[0]
        bb_sharded = shard_map_compat(
            lambda p, l, tt, e, r: mmdit_apply(p, cfg, l, tt, e, r),
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(None, axis)),
            out_specs=P(axis),
        )
        cn_sharded = shard_map_compat(
            lambda p, l, cnd, tt, e: controlnet_apply(p, cfg, l, cnd, tt, e),
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(None, axis),
        )

        def run(pb, pcns, lat, emb, cond, t_mid, t_cur, t_next, guidance):
            b = lat.shape[0]
            rows = b * (2 if uses_cfg else 1)
            # mode is static at trace time (shapes known): batch-row DP
            # when the folded rows divide k, else sequence sharding with
            # per-layer K/V all-gathers (mirrors the unfused backbone)
            row_dp = rows % k == 0

            def body(lat, xs):
                t, tc, tn = xs
                if uses_cfg:     # fold CFG onto the batch axis, then shard
                    lat2 = jnp.concatenate([lat, lat], axis=0)
                    t2 = jnp.concatenate([t, t], axis=0)
                    emb_b = jnp.concatenate([emb, jnp.zeros_like(emb)], axis=0)
                else:
                    lat2, t2, emb_b = lat, t, emb
                if n_cns:
                    # ControlNet sees the COND embedding on every row (the
                    # unfused graph computes one residual set and reuses it
                    # for both CFG branches; duplicated rows reproduce that
                    # bitwise, and they divide k when the CFG pair does)
                    cond2 = (jnp.concatenate([cond, cond], axis=0)
                             if uses_cfg else cond)
                    emb_cn = (jnp.concatenate([emb, emb], axis=0)
                              if uses_cfg else emb)
                    res2 = None
                    for pcn in pcns:
                        r = (cn_sharded(pcn, lat2, cond2, t2, emb_cn)
                             if row_dp else
                             controlnet_apply(pcn, cfg, lat2, cond2, t2,
                                              emb_cn))
                        res2 = r if res2 is None else res2 + r
                else:
                    res2 = jnp.zeros(
                        (cfg.n_layers, lat2.shape[0], cfg.image_tokens,
                         cfg.d_model), cfg.dtype)
                if row_dp:
                    v2 = bb_sharded(pb, lat2, t2, emb_b, res2)
                else:
                    v2 = mmdit_apply_seq_sharded(pb, cfg, lat2, t2, emb_b,
                                                 res2, mesh)
                if uses_cfg:
                    v_c, v_u = v2[:b], v2[b:]
                    g = guidance.astype(v2.dtype)
                    g = g.reshape((b,) + (1,) * (v2.ndim - 1))
                    v = cfg_combine(v_u, v_c, g)
                else:
                    v = v2
                dt = (tn - tc).astype(lat.dtype)
                dt = dt.reshape((lat.shape[0],) + (1,) * (lat.ndim - 1))
                return lat + dt * v, None

            lat, _ = jax.lax.scan(body, lat, (t_mid, t_cur, t_next))
            return lat

        return run

    # ------------------------------------------------------------ costing
    def cost(self) -> ModelCost:
        """PER-STEP terms (backbone + attached ControlNets fused into the
        scan body) with ``steps_per_call`` carrying the segment length;
        only the final latent leaves the device per chunk."""
        b = self.backbone.cost()
        flops = b.flops_per_item
        params = b.param_bytes
        act = b.act_io_bytes
        for cn in self.cns:
            c = cn.cost()
            flops += c.flops_per_item
            params += c.param_bytes
            act += c.act_io_bytes
        return ModelCost(
            flops_per_item=flops,
            param_bytes=params,
            act_io_bytes=act,
            output_bytes=self.family.latent_bytes(),
            max_parallelism=b.max_parallelism,
            max_batch=b.max_batch,
            calls_per_request=1,
            steps_per_call=self.n_steps,
            # per-row adapters apply inside every scan step — inherit the
            # backbone's per-step multi-LoRA pricing terms
            lora_rank=b.lora_rank,
            lora_flops_per_rank=b.lora_flops_per_rank,
            lora_bytes_per_adapter=b.lora_bytes_per_adapter,
            # the fused chain is backbone + controlnets end to end — every
            # constituent quantizes, so the segment prices quantized too
            quantizable=True,
        )


class LoRAAdapter(Model):
    """Weight-patching adapter (attached via ``backbone.add_patch``)."""

    def __init__(self, family: DiffusionFamily, name: str = "style",
                 rank: int = 8, param_bytes: float = 886 * 2**20) -> None:
        self.family = family
        self.rank = rank
        self._param_bytes = param_bytes
        super().__init__(model_id=f"lora:{name}:{family.name}")

    def setup_io(self) -> None:
        self.add_output("adapter_weights", TensorType())

    def load(self, device: Any = None) -> Dict[str, Any]:
        key = jax.random.PRNGKey(stable_hash(self.model_id) % 2**31)
        lora = init_lora(key, self.family.dit, rank=self.rank)
        # quantized factors (REPRO_QUANT): the AdapterPool's byte budget
        # and the proc plane's adapter ships both see the small form
        return {
            "lora": quantize_lora(randomize_lora(key, lora)),
            # companion factors for a patched TextEncoder (grouped or
            # folded into the last layer's wo); unused unless the adapter
            # is attached to the text encoder as well
            "text_lora": quantize_text_lora(init_text_lora(
                jax.random.fold_in(key, 1), self.family.dit.text_dim,
                rank=self.rank)),
        }

    def execute(self, model_components: Dict[str, Any], **kw: Any) -> Dict[str, Any]:
        return {"adapter_weights": model_components["lora"]}

    def cost(self) -> ModelCost:
        return ModelCost(0, self._param_bytes, self._param_bytes,
                         self._param_bytes, max_batch=1)
