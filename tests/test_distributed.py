"""Distributed-lowering tests on virtual device meshes (subprocess-spawned
so the 1-device pytest process keeps its device count)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, devices: int = 8, timeout: int = 900) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(snippet)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_latent_parallel_cfg_matches_sequential():
    """shard_map latent parallelism == sequential CFG (paper Fig 2)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.diffusion.config import FAMILIES
        from repro.diffusion.mmdit import init_mmdit
        from repro.diffusion.sampler import cfg_velocity, latent_parallel_velocity
        cfg = FAMILIES['sd3'].dit
        params = init_mmdit(jax.random.PRNGKey(0), cfg)
        lat = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 4))
        emb = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 64))
        null = jnp.zeros_like(emb)
        t = jnp.full((1,), 0.7)
        seq = cfg_velocity(params, cfg, lat, t, emb, null, guidance=3.0)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ('cfg',))
        par = latent_parallel_velocity(mesh, params, cfg, lat, t, emb, null,
                                       guidance=3.0)
        err = float(jnp.abs(seq - par).max())
        assert err < 1e-4, err
        print('OK', err)
    """, devices=2)
    assert "OK" in out


def test_reduced_arch_lowers_on_virtual_mesh():
    """A reduced dense arch train step lowers+compiles on a 2x4 mesh with
    the production sharding rules."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.launch import sharding as shd
        from repro.models import get_family, make_train_step
        from repro.train.optimizer import adamw_init
        cfg = ARCHS['qwen3-1.7b'].reduced()
        fam = get_family(cfg)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ('data', 'model'))
        params = jax.eval_shape(lambda k: fam.init(k, cfg, jnp.float32),
                                jax.random.PRNGKey(0))
        pspecs = shd.sanitize(shd.param_specs(cfg, params), params, mesh)
        opt = jax.eval_shape(adamw_init, params)
        ospecs = shd.sanitize(shd.opt_state_specs(pspecs), opt, mesh)
        named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        batch = {'tokens': jax.ShapeDtypeStruct((4, 32), jnp.int32),
                 'labels': jax.ShapeDtypeStruct((4, 32), jnp.int32)}
        bspec = {'tokens': NamedSharding(mesh, P('data', None)),
                 'labels': NamedSharding(mesh, P('data', None))}
        step = make_train_step(cfg)
        lowered = jax.jit(step, in_shardings=(named(pspecs), named(ospecs), bspec),
                          out_shardings=(named(pspecs), named(ospecs),
                                         NamedSharding(mesh, P()))
                          ).lower(params, opt, batch)
        compiled = lowered.compile()
        print('OK flops', compiled.cost_analysis()[0].get('flops', 0)
              if isinstance(compiled.cost_analysis(), (list, tuple))
              else compiled.cost_analysis().get('flops', 0))
    """, devices=8)
    assert "OK" in out


def test_flash_decode_shardmap_matches_reference():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, math
        from jax.sharding import Mesh
        from repro.models.transformer import _flash_decode_shardmap
        from repro.nn.layers import gqa_attention
        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ('data', 'model'))
        key = jax.random.PRNGKey(0)
        b, hq, hkv, hd, S = 4, 8, 2, 16, 32
        q = jax.random.normal(key, (b, 1, hq, hd))
        kn = jax.random.normal(key, (b, 1, hkv, hd))
        vn = jax.random.normal(key, (b, 1, hkv, hd))
        ck = jax.random.normal(key, (b, S, hkv, hd))
        cv = jax.random.normal(key, (b, S, hkv, hd))
        pos = jnp.asarray(13)
        out, ck2, cv2 = jax.jit(lambda *a: _flash_decode_shardmap(
            (mesh, 'model', 'data'), *a, window=None))(q, kn, vn, ck, cv, pos)
        ck_ref = ck.at[:, 13].set(kn[:, 0])
        cv_ref = cv.at[:, 13].set(vn[:, 0])
        neg = jnp.finfo(jnp.float32).min
        mask = jnp.where(jnp.arange(S)[None, None, None, :] <= 13, 0.0, neg)
        mask = jnp.broadcast_to(mask, (b, 1, 1, S))
        ref = gqa_attention(q, ck_ref, cv_ref, mask=mask)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-4, err
        print('OK', err)
    """, devices=4)
    assert "OK" in out


def test_dryrun_single_pair_cli():
    """The dry-run CLI end to end on the smallest pair (512 devices)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "whisper-tiny", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK   whisper-tiny x decode_32k" in out.stdout
