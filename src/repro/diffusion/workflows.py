"""Workflow builders (Table 2's S1-S6) over the servable component models.

The component :class:`~repro.core.model.Model` subclasses live in
:mod:`repro.diffusion.ops`; this module composes them into the paper's
Basic / +ControlNet / +LoRA workflow templates.  ``repro.diffusion.serving``
re-exports both for backwards compatibility.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from repro.core.model import Model
from repro.core.types import Image
from repro.core.workflow import WorkflowTemplate, compose
from repro.diffusion.config import DiffusionFamily, FAMILIES
from repro.diffusion.ops import (
    ControlNet,
    DenoiseStep,
    DiffusionBackbone,
    LatentsGenerator,
    LoRAAdapter,
    ResidualCombine,
    TextEncoder,
    VAEDecode,
    VAEEncode,
)
from repro.diffusion.sampler import flow_schedule


class ModelSet:
    """Shared model instances for one family (sharing is by model_id)."""

    def __init__(self, family: DiffusionFamily) -> None:
        self.family = family
        self.latents = LatentsGenerator(family)
        self.text_enc = TextEncoder(family)
        self.backbone = DiffusionBackbone(family)
        self.cn1 = ControlNet(family, 1)
        self.cn2 = ControlNet(family, 2)
        self.vae_dec = VAEDecode(family)
        self.vae_enc = VAEEncode(family)
        self.denoise = DenoiseStep(family)
        self.combine = ResidualCombine(family)


def _denoising_loop(ms: ModelSet, wf, lat, emb, steps: int, guidance: float,
                    controlnets: List[Model], cond_lat) -> Any:
    sched = [float(x) for x in flow_schedule(steps,
                                             ms.family.dit.schedule_shift)]
    for i in range(steps):
        t_cur, t_next = sched[i], sched[i + 1]
        res = None
        for cn in controlnets:
            r = cn(lat, cond_lat, emb, t_cur)
            res = r if res is None else ms.combine(res, r)
        v = ms.backbone(
            latents=lat, prompt_embeds=emb, t=t_cur,
            controlnet_residuals=res, guidance=guidance,
        )
        lat = ms.denoise(v, lat, t_cur, t_next)
    return lat


def make_basic_workflow(family_name: str, ms: Optional[ModelSet] = None) -> WorkflowTemplate:
    family = FAMILIES[family_name]
    ms = ms or ModelSet(family)

    @compose(f"{family.name}:basic")
    def wf_fn(wf, steps=family.denoise_steps, guidance=4.5):
        seed = wf.add_input("seed", int)
        prompt = wf.add_input("prompt", str)
        lat = ms.latents(seed)
        emb = ms.text_enc(prompt)
        lat = _denoising_loop(ms, wf, lat, emb, steps, guidance, [], None)
        img = ms.vae_dec(lat)
        wf.add_output(img, name="image")

    return wf_fn


def make_controlnet_workflow(
    family_name: str, n_controlnets: int = 1, ms: Optional[ModelSet] = None
) -> WorkflowTemplate:
    family = FAMILIES[family_name]
    ms = ms or ModelSet(family)
    cns = [ms.cn1, ms.cn2][:n_controlnets]

    @compose(f"{family.name}:cn{n_controlnets}")
    def wf_fn(wf, steps=family.denoise_steps, guidance=4.5):
        seed = wf.add_input("seed", int)
        prompt = wf.add_input("prompt", str)
        ref_image = wf.add_input("ref_image", Image)
        lat = ms.latents(seed)
        emb = ms.text_enc(prompt)
        cond = ms.vae_enc(ref_image)
        lat = _denoising_loop(ms, wf, lat, emb, steps, guidance, cns, cond)
        img = ms.vae_dec(lat)
        wf.add_output(img, name="image")

    return wf_fn


def make_lora_workflow(
    family_name: str, lora_name: str = "style", ms: Optional[ModelSet] = None
) -> WorkflowTemplate:
    family = FAMILIES[family_name]
    ms = ms or ModelSet(family)
    # a fresh backbone instance so the patch does not leak into other
    # workflows sharing the ModelSet (model_id stays identical -> shareable)
    backbone = DiffusionBackbone(family)
    lora = LoRAAdapter(family, lora_name)
    backbone.add_patch(lora)
    patched = ModelSet(family)
    patched.backbone = backbone
    patched.latents, patched.text_enc = ms.latents, ms.text_enc
    patched.vae_dec, patched.denoise = ms.vae_dec, ms.denoise

    @compose(f"{family.name}:lora:{lora_name}")
    def wf_fn(wf, steps=family.denoise_steps, guidance=4.5):
        seed = wf.add_input("seed", int)
        prompt = wf.add_input("prompt", str)
        lat = patched.latents(seed)
        emb = patched.text_enc(prompt)
        lat = _denoising_loop(patched, wf, lat, emb, steps, guidance, [], None)
        img = patched.vae_dec(lat)
        wf.add_output(img, name="image")

    return wf_fn


def table2_setting(setting: str) -> Dict[str, WorkflowTemplate]:
    """S1-S6 from Table 2: per-family (Basic, +C.N.1, +C.N.2) workflows."""
    singles = {"s1": ["sd3"], "s2": ["sd3.5-large"], "s3": ["flux-schnell"],
               "s4": ["flux-dev"], "s5": ["sd3", "sd3.5-large"],
               "s6": ["flux-schnell", "flux-dev"]}
    fams = singles[setting.lower()]
    out: Dict[str, WorkflowTemplate] = {}
    for f in fams:
        ms = ModelSet(FAMILIES[f])
        basic = make_basic_workflow(f, ms)
        cn1 = make_controlnet_workflow(f, 1, ms)
        cn2 = make_controlnet_workflow(f, 2, ms)
        out[basic.name] = basic
        out[cn1.name] = cn1
        out[cn2.name] = cn2
    return out
