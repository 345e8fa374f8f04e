"""Sharded execution plane: MeshManager partitioning/clamping logic,
multi-device parity (sharded k=2/4 outputs == single-device outputs), and
real device placement of scheduled batches.

Pure-logic tests run everywhere; multi-device tests run in-process when
the host has >= 4 devices (the CI job forces 8 virtual CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and are ALSO
covered on 1-device hosts by subprocess tests that force the device
count, mirroring tests/test_distributed.py."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import MeshManager, ShardedBackend
from repro.core.mesh import sharded_exec_enabled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multi_device = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (CI mesh job forces 8 virtual CPU devices)")


def _run(snippet: str, devices: int = 8, timeout: int = 900,
         env_extra=None) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(snippet)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# --------------------------------------------------------------------------
# MeshManager partitioning / clamping (pure logic, fake devices)
# --------------------------------------------------------------------------

def test_mesh_manager_partitions_devices_per_executor():
    d = [object() for _ in range(4)]
    mm = MeshManager(devices=d)
    assert mm.device_of(0) is d[0] and mm.device_of(3) is d[3]
    assert mm.device_of(4) is d[0]          # fleet larger than host: wrap
    assert mm.devices_of([0, 1, 4, 5]) == [d[0], d[1]]   # dedup, ordered
    assert mm.assemblable([0, 1, 2]) == 3
    assert mm.assemblable([0, 4]) == 1      # same device twice
    assert mm.max_k() == 4


def test_mesh_manager_clamp_and_disable(monkeypatch):
    mm = MeshManager(devices=[object(), object()])
    assert mm.clamp(4, [0, 1, 2]) == 2
    assert mm.clamp(1, [0]) == 1
    monkeypatch.setenv("REPRO_SHARDED_EXEC", "0")
    assert not sharded_exec_enabled()
    assert mm.clamp(4, [0, 1]) == 1
    assert mm.max_k() == 1


def test_sharded_backend_single_device_degrades_to_local():
    """On a 1-device host (or with sharding disabled) the backend is a
    plain LocalBackend: no mesh, no shard log, identical outputs."""
    from repro.diffusion import FAMILIES, ModelSet

    mm = MeshManager(devices=jax.devices()[:1])
    backend = ShardedBackend(mm)
    assert not backend.enabled
    ms = ModelSet(FAMILIES["sd3"])
    cfg = FAMILIES["sd3"].dit
    kw = {"latents": jax.random.normal(
              jax.random.PRNGKey(0),
              (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)),
          "prompt_embeds": jax.random.normal(
              jax.random.PRNGKey(1), (1, cfg.text_tokens, cfg.text_dim)),
          "t": 0.5, "guidance": 4.0}
    outs, _, _ = backend.execute_batch(ms.backbone, [kw])
    ref = ms.backbone.execute(backend.ensure_loaded(ms.backbone)[0], **kw)
    np.testing.assert_allclose(np.asarray(outs[0]["velocity"]),
                               np.asarray(ref["velocity"]), atol=1e-5)
    assert backend.shard_log == []


# --------------------------------------------------------------------------
# In-process multi-device parity (CI mesh job: 8 virtual devices)
# --------------------------------------------------------------------------

def _backbone_kwargs(n, cfg):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 2 * n)
    return [{
        "latents": jax.random.normal(
            ks[2 * i], (1, cfg.latent_size, cfg.latent_size,
                        cfg.latent_channels)),
        "prompt_embeds": jax.random.normal(
            ks[2 * i + 1], (1, cfg.text_tokens, cfg.text_dim)),
        "t": 0.25 + 0.1 * i,
        "guidance": 3.0 + i,             # heterogeneous per-item guidance
    } for i in range(n)]


@multi_device
@pytest.mark.parametrize("k,n_req", [(2, 1), (2, 3), (4, 2)])
def test_backbone_sharded_parity(k, n_req):
    """Sharded stacked forward (k=2: CFG-branch split; k=4: row or
    sequence sharding) matches the single-device stacked forward."""
    from repro.diffusion import FAMILIES, ModelSet

    ms = ModelSet(FAMILIES["sd3"])
    mm = MeshManager()
    backend = ShardedBackend(mm)
    kws = _backbone_kwargs(n_req, FAMILIES["sd3"].dit)
    ref, _, _ = backend.execute_batch(ms.backbone, [dict(kw) for kw in kws])
    mesh = mm.submesh(list(range(k)))
    out, _, _ = backend.execute_batch(ms.backbone, [dict(kw) for kw in kws],
                                      mesh=mesh)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o["velocity"]),
                                   np.asarray(r["velocity"]),
                                   atol=1e-4, rtol=1e-4)
    assert backend.shard_log[-1][2] == k
    assert len(set(backend.shard_log[-1][3])) == k


@multi_device
def test_seq_sharded_mmdit_device_placement_and_parity():
    """The sequence-sharded forward really spans the submesh (output is
    sharded over all k devices) and matches the unsharded forward."""
    import jax.numpy as jnp
    from repro.diffusion import FAMILIES
    from repro.diffusion.mmdit import init_mmdit, mmdit_apply, mmdit_apply_seq_sharded

    cfg = FAMILIES["sd3"].dit
    params = init_mmdit(jax.random.PRNGKey(0), cfg)
    lat = jax.random.normal(jax.random.PRNGKey(1),
                            (2, cfg.latent_size, cfg.latent_size,
                             cfg.latent_channels))
    emb = jax.random.normal(jax.random.PRNGKey(2),
                            (2, cfg.text_tokens, cfg.text_dim))
    t = jnp.full((2,), 0.6)
    mm = MeshManager()
    mesh = mm.submesh([0, 1, 2, 3])
    out = mmdit_apply_seq_sharded(params, cfg, lat, t, emb, None, mesh)
    ref = mmdit_apply(params, cfg, lat, t, emb, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    assert out.sharding.device_set == set(np.asarray(mesh.devices).ravel())
    assert len(out.sharding.device_set) == 4


@multi_device
def test_controlnet_and_vae_sharded_parity():
    from repro.diffusion import FAMILIES, ModelSet

    fam = FAMILIES["sd3"]
    cfg = fam.dit
    ms = ModelSet(fam)
    mm = MeshManager()
    backend = ShardedBackend(mm)
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 6)
    shape = (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)
    cn_kws = [{
        "latents": jax.random.normal(ks[2 * i], shape),
        "cond_latents": jax.random.normal(ks[2 * i + 1], shape),
        "prompt_embeds": jax.random.normal(
            ks[4 + i], (1, cfg.text_tokens, cfg.text_dim)),
        "t": 0.5,
    } for i in range(2)]
    ref, _, _ = backend.execute_batch(ms.cn1, [dict(k_) for k_ in cn_kws])
    out, _, _ = backend.execute_batch(ms.cn1, [dict(k_) for k_ in cn_kws],
                                      mesh=mm.submesh([0, 1]))
    for o, r in zip(out, ref):
        np.testing.assert_allclose(
            np.asarray(o["controlnet_residuals"]),
            np.asarray(r["controlnet_residuals"]), atol=1e-4, rtol=1e-4)

    vae_kws = [{"latents": jax.random.normal(k_, shape)}
               for k_ in jax.random.split(key, 4)]
    ref, _, _ = backend.execute_batch(ms.vae_dec, [dict(k_) for k_ in vae_kws])
    out, _, _ = backend.execute_batch(ms.vae_dec, [dict(k_) for k_ in vae_kws],
                                      mesh=mm.submesh([0, 1, 2, 3]))
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o["image"]),
                                   np.asarray(r["image"]),
                                   atol=1e-4, rtol=1e-4)
    assert backend.shard_log[-1][:3] == ("vae:sd3", 4, 4)


@multi_device
def test_indivisible_batch_falls_back_to_single_device():
    """3 CFG rows on k=4 divide by neither mode at odd token grids; here
    the toy grid divides, so force indivisibility via a k=3 submesh: 3
    requests -> 6 rows (divisible: DP) but 1 request -> 2 rows, and the
    8-row patch grid % 3 != 0 -> clean fallback, no sharded forward."""
    from repro.diffusion import FAMILIES, ModelSet

    ms = ModelSet(FAMILIES["sd3"])
    mm = MeshManager()
    backend = ShardedBackend(mm)
    kws = _backbone_kwargs(1, FAMILIES["sd3"].dit)
    out, _, _ = backend.execute_batch(ms.backbone, [dict(kw) for kw in kws],
                                      mesh=mm.submesh([0, 1, 2]))
    assert backend.shard_log == []          # declined -> single-device path
    ref, _, _ = backend.execute_batch(ms.backbone, [dict(kw) for kw in kws])
    np.testing.assert_allclose(np.asarray(out[0]["velocity"]),
                               np.asarray(ref[0]["velocity"]), atol=1e-5)


# --------------------------------------------------------------------------
# Subprocess coverage (always runs, forces an 8-device child like
# tests/test_distributed.py, so 1-device tier-1 still exercises the plane)
# --------------------------------------------------------------------------

def test_scheduled_k4_batch_executes_on_4_device_submesh():
    """Acceptance: with 8 forced host devices, a k=4 ScheduledBatch
    executes on a 4-device submesh (placement asserted via the scheduler's
    executor set, the MeshManager's device map, and the backend's shard
    log) and its outputs match a single-device run bit-for-bit-ish."""
    out = _run("""
        import jax, numpy as np
        from repro.core import LocalBackend, Scheduler, ServingSystem, ShardedBackend
        from repro.diffusion import make_basic_workflow

        def serve(backend, n_exec, fixed_k=None):
            sys_ = ServingSystem(n_executors=n_exec, backend=backend)
            if fixed_k:
                sys_.coordinator.scheduler = Scheduler(
                    sys_.profiles, fixed_parallelism=fixed_k,
                    use_declared_max_batch=True,
                    mesh=getattr(backend, 'mesh_manager', None))
            wf = make_basic_workflow('sd3')
            sys_.register(wf)
            reqs = [sys_.submit(wf.name, inputs={'seed': i, 'prompt': f'p {i}'},
                                arrival=0.0, steps=2) for i in range(2)]
            sys_.run()
            imgs = [np.asarray(sys_.coordinator.engine.value_of(
                r.ref_key(r.graph.outputs['image']))) for r in reqs]
            assert all(r.status == 'done' for r in reqs)
            return imgs, sys_

        single, _ = serve(LocalBackend(), 1)
        backend = ShardedBackend()
        sharded, sys_ = serve(backend, 4, fixed_k=4)
        for a, b in zip(single, sharded):
            err = float(np.abs(a - b).max())
            assert err < 1e-4, err
        k4 = [d for d in sys_.coordinator.dispatch_log
              if d.model_id == 'segment:backbone:sd3']
        assert k4 and all(d.parallelism == 4 for d in k4), k4
        for d in k4:
            assert len(set(d.executor_ids)) == 4
            devs = {backend.mesh_manager.device_of(e).id for e in d.executor_ids}
            assert len(devs) == 4, devs
        assert any(s[0] == 'segment:backbone:sd3' and s[2] == 4
                   and len(set(s[3])) == 4 for s in backend.shard_log)
        print('OK', len(backend.shard_log))
    """, devices=8)
    assert "OK" in out


def test_sharded_exec_flag_disables_sharding():
    """REPRO_SHARDED_EXEC=0: same workload, no sharded forwards, same
    outputs — the CPU-CI fallback rule."""
    out = _run("""
        import os
        os.environ['REPRO_SHARDED_EXEC'] = '0'
        import numpy as np
        from repro.core import Scheduler, ServingSystem, ShardedBackend
        from repro.diffusion import make_basic_workflow
        backend = ShardedBackend()
        assert not backend.enabled
        sys_ = ServingSystem(n_executors=4, backend=backend)
        wf = make_basic_workflow('sd3')
        sys_.register(wf)
        r = sys_.submit(wf.name, inputs={'seed': 0, 'prompt': 'p'},
                        arrival=0.0, steps=2)
        sys_.run()
        assert r.status == 'done'
        assert backend.shard_log == []
        assert all(d.parallelism == 1 for d in sys_.coordinator.dispatch_log)
        img = np.asarray(sys_.coordinator.engine.value_of(
            r.ref_key(r.graph.outputs['image'])))
        assert np.isfinite(img).all()
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_controlnet_workflow_sharded_end_to_end():
    """ControlNet + backbone + VAE all shard (adaptive parallelism, idle
    fleet) inside one workflow and the final image matches the
    single-device plane."""
    out = _run("""
        import numpy as np
        from repro.core import LocalBackend, ServingSystem, ShardedBackend
        from repro.diffusion import make_controlnet_workflow

        def serve(backend, n_exec):
            sys_ = ServingSystem(n_executors=n_exec, backend=backend)
            wf = make_controlnet_workflow('sd3', 1)
            sys_.register(wf)
            reqs = [sys_.submit(wf.name,
                                inputs={'seed': i, 'prompt': 'cn', 'ref_image': None},
                                arrival=0.0, steps=2) for i in range(2)]
            sys_.run()
            assert all(r.status == 'done' for r in reqs)
            return [np.asarray(sys_.coordinator.engine.value_of(
                r.ref_key(r.graph.outputs['image']))) for r in reqs]

        single = serve(LocalBackend(), 1)
        backend = ShardedBackend()
        sharded = serve(backend, 4)
        for a, b in zip(single, sharded):
            err = float(np.abs(a - b).max())
            assert err < 1e-4, err
        models = sorted({s[0] for s in backend.shard_log})
        assert 'segment:backbone:sd3+controlnet1:sd3' in models, models
        print('OK', models)
    """, devices=4)
    assert "OK" in out
