import os
if __name__ == "__main__":
    # Script-only (see repro.launch.dryrun): importing this module must
    # not mutate the process env out from under spawned workers.
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run for the paper's OWN models: one denoising step of the
real-scale backbone (MMDiT with CFG, or FLUX with embedded guidance) on
the production mesh.

Latent (CFG) parallelism appears here as the batch dimension carrying
both guidance branches (2B rows over the ``data`` axis — the general
form of the paper's 2-GPU split), with tensor parallelism over ``model``;
a FLUX step has one row per request.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun_diffusion --family sd3
    PYTHONPATH=src python -m repro.launch.dryrun_diffusion --all
"""

import argparse
import sys
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.diffusion.config import FAMILIES
from repro.diffusion.flux import flux_apply, init_flux
from repro.diffusion.mmdit import init_mmdit, mmdit_apply
from repro.launch.dryrun import analyze
from repro.launch.mesh import make_production_mesh

# published backbone geometries (flux-schnell shares flux-dev's)
REAL = {f: FAMILIES[f].published for f in ("sd3", "sd3.5-large", "flux-dev")}

_DOWN = ("wo", "w2", "final_proj")


def _specs(params: Any) -> Any:
    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = len(leaf.shape)
        if nd <= 1 or "norm" in name or name.endswith("_b"):
            return P()
        lead = (None,) * (nd - 2)
        if any(k in name for k in _DOWN):
            return P(*lead, "model", None)
        return P(*lead, None, "model")

    return jax.tree_util.tree_map_with_path(spec, params)


def build(family: str, batch: int = 8, mesh=None):
    cfg = REAL[family]
    flux = cfg.architecture == "flux"
    init = init_flux if flux else init_mmdit
    mesh = mesh or make_production_mesh()
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = dp if len(dp) > 1 else dp[0]
    params_shape = jax.eval_shape(
        lambda k: init(k, cfg), jax.random.PRNGKey(0))
    pspecs = _specs(params_shape)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    b2 = batch * (1 if flux else 2)       # CFG: cond + uncond rows
    args = (
        jax.ShapeDtypeStruct(
            (b2, cfg.latent_size, cfg.latent_size, cfg.latent_channels),
            jnp.bfloat16),
        jax.ShapeDtypeStruct((b2,), jnp.float32),
        jax.ShapeDtypeStruct((b2, cfg.text_tokens, cfg.text_dim), jnp.bfloat16),
    )
    in_specs = (NamedSharding(mesh, P(dp, None, None, None)),
                NamedSharding(mesh, P(dp)),
                NamedSharding(mesh, P(dp, None, None)))

    def denoise_step(params, latents, t, text_emb):
        if flux:     # guidance rides in as an input, here 3.5 per row
            return flux_apply(params, cfg, latents, t, text_emb,
                              jnp.full_like(t, 3.5))
        return mmdit_apply(params, cfg, latents, t, text_emb)

    jitted = jax.jit(
        denoise_step,
        in_shardings=(named(pspecs),) + in_specs,
        out_shardings=NamedSharding(mesh, P(dp, None, None, None)),
    )
    lowered = jitted.lower(params_shape, *args)
    n_params = sum(x.size for x in jax.tree.leaves(params_shape))
    meta = {"arch": f"diffusion:{family}",
            "shape": f"denoise_b{batch}" + ("" if flux else "_cfg"),
            "mesh": "x".join(map(str, mesh.devices.shape)), "kind": "prefill",
            "params": float(n_params), "active_params": float(n_params)}
    return lowered, meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    fams = list(REAL) if args.all else [args.family or "sd3"]
    fail = 0
    for f in fams:
        try:
            lowered, meta = build(f, args.batch)
            r = analyze(lowered, meta)
            peak = r["bytes_per_device"].get("peak") or 0
            print(f"OK   diffusion:{f}: params={meta['params']/1e9:.1f}B "
                  f"flops/part={r['hlo_flops']:.3e} "
                  f"coll={r['collectives'].get('total', 0):.3e} "
                  f"peak/device={peak/2**30:.2f}GiB", flush=True)
        except Exception as e:
            fail += 1
            print(f"FAIL diffusion:{f}: {type(e).__name__}: {e}", flush=True)
    sys.exit(1 if fail else 0)


if __name__ == "__main__":
    main()
