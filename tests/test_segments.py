"""Segment compiler: fusion pass structure, load-adaptive chunking, and
the acceptance parity suite — fused ``DenoiseSegment`` execution (chunked
and full) matches the unfused per-step graph BIT-EXACTLY on the
executable plane, for basic, cn1/cn2 and LoRA workflows."""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.core import (
    GraphCompiler,
    LocalBackend,
    ProfileStore,
    Scheduler,
    SegmentFusionPass,
    ServingSystem,
    default_passes,
)
from repro.core.passes import (
    ApproximateCachingPass,
    AsyncLoRAPass,
    InlineTrivialPass,
    JitCompilePass,
)
from repro.core.profiles import GPU_H800
from repro.diffusion import (
    ApproxCache,
    FAMILIES,
    ModelSet,
    make_basic_workflow,
    make_controlnet_workflow,
    make_lora_workflow,
)

# adapter fetch resolves (sim-time) before any measured dispatch finishes,
# so fused and unfused arms both run every step patched
FAST_FETCH = dataclasses.replace(GPU_H800, remote_bw=1e18)

UNFUSED = [InlineTrivialPass(), AsyncLoRAPass(), JitCompilePass()]


def _serve(wf, inputs_list, steps, fused=True, segment_chunk=None,
           hw=GPU_H800, n_exec=2):
    backend = LocalBackend()
    sys_ = ServingSystem(n_executors=n_exec, backend=backend, hw=hw)
    if not fused:
        sys_.registry.compiler = GraphCompiler(list(UNFUSED))
    if segment_chunk is not None:
        sys_.coordinator.scheduler = Scheduler(
            sys_.profiles, use_declared_max_batch=True,
            segment_chunk=segment_chunk)
    sys_.register(wf)
    reqs = [sys_.submit(wf.name, inputs=inp, arrival=0.0, steps=steps)
            for inp in inputs_list]
    sys_.run()
    assert all(r.status == "done" for r in reqs)
    imgs = [np.asarray(sys_.coordinator.engine.value_of(
        r.ref_key(r.graph.outputs["image"]))) for r in reqs]
    return imgs, sys_, backend


# --------------------------------------------------------------------------
# Fusion pass structure
# --------------------------------------------------------------------------

def test_fusion_rewrites_basic_chain_to_one_segment():
    wf = make_basic_workflow("sd3")
    graph = GraphCompiler(default_passes()).compile(wf.instantiate(steps=6))
    segs = graph.nodes_of_model("segment:backbone:sd3")
    assert len(segs) == 1
    assert graph.nodes_of_model("backbone:sd3") == []
    assert graph.nodes_of_model("denoise_step") == []
    node = segs[0]
    assert len(node.inputs["t_mid"]) == 6
    assert len(node.inputs["t_next"]) == 6
    assert node.inputs["t_mid"][0] == 1.0 and node.inputs["t_next"][-1] == 0.0
    assert node.attrs.get("jit")


def test_fusion_rewrites_cn2_chain_with_residual_tree():
    wf = make_controlnet_workflow("sd3", 2)
    graph = GraphCompiler(default_passes()).compile(wf.instantiate(steps=4))
    seg_id = "segment:backbone:sd3+controlnet1:sd3+controlnet2:sd3"
    assert len(graph.nodes_of_model(seg_id)) == 1
    for mid in ("backbone:sd3", "controlnet1:sd3", "controlnet2:sd3",
                "residual_combine", "denoise_step"):
        assert graph.nodes_of_model(mid) == [], mid
    # conditioning path (vae encode) survives and feeds the segment
    assert graph.nodes_of_model("vae:sd3")


def test_fusion_disabled_by_env(monkeypatch):
    monkeypatch.setenv("REPRO_SEGMENT_FUSION", "0")
    wf = make_basic_workflow("sd3")
    graph = GraphCompiler(default_passes()).compile(wf.instantiate(steps=4))
    assert len(graph.nodes_of_model("backbone:sd3")) == 4
    assert graph.nodes_of_model("segment:backbone:sd3") == []


def test_fusion_noop_on_sim_toy_models(toy_workflow):
    """Models without scan_role declarations never fuse."""
    graph = GraphCompiler(default_passes()).compile(
        toy_workflow.instantiate(steps=4))
    assert len(graph.nodes_of_model("backbone")) == 4


def test_fusion_composes_with_approx_cache_shortened_chain():
    """ApproximateCaching + AsyncLoRA + SegmentFusion on cn2: the cache
    skip shortens the first (only) segment and the DAG stays valid."""
    cache = ApproxCache(similarity_threshold=0.0)
    lat = jax.random.normal(jax.random.PRNGKey(9), (1, 16, 16, 4))
    cache.insert("warm", 2, lat)
    passes = [ApproximateCachingPass(cache, "backbone:sd3", skip_fraction=0.5),
              InlineTrivialPass(), AsyncLoRAPass(), SegmentFusionPass(),
              JitCompilePass()]
    wf = make_controlnet_workflow("sd3", 2)
    graph = GraphCompiler(passes).compile(wf.instantiate(steps=4))
    graph.validate()
    seg_id = "segment:backbone:sd3+controlnet1:sd3+controlnet2:sd3"
    segs = graph.nodes_of_model(seg_id)
    assert len(segs) == 1
    assert len(segs[0].inputs["t_mid"]) == 2          # 4 steps - 2 skipped
    assert len(graph.nodes_of_model("approx_cache_lookup")) == 1
    # segment consumes the cache lookup's latent, not the random init
    assert graph.nodes_of_model("latents_generator") == []


# --------------------------------------------------------------------------
# Acceptance parity: fused == unfused, bit-exact, executable plane
# --------------------------------------------------------------------------

@pytest.mark.parametrize("wf_maker,inputs", [
    (lambda: make_basic_workflow("sd3"),
     [{"seed": 0, "prompt": "a fox"}, {"seed": 1, "prompt": "two foxes"}]),
    (lambda: make_controlnet_workflow("sd3", 1),
     [{"seed": 0, "prompt": "cn", "ref_image": None}]),
    (lambda: make_controlnet_workflow("sd3", 2),
     [{"seed": 2, "prompt": "cn2", "ref_image": None}]),
], ids=["basic", "cn1", "cn2"])
def test_segment_parity_bitexact(wf_maker, inputs):
    """steps=5 puts non-dyadic dt values on the schedule — the hard case
    for contraction (FMA) agreement between the scan and per-step paths."""
    unfused, _, _ = _serve(wf_maker(), inputs, steps=5, fused=False)
    full, sys_full, _ = _serve(wf_maker(), inputs, steps=5, fused=True)
    chunk4, sys_c4, _ = _serve(wf_maker(), inputs, steps=5, fused=True,
                               segment_chunk=4)
    for got, want in zip(full, unfused):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(chunk4, unfused):
        np.testing.assert_array_equal(got, want)
    # every request's 5-step schedule ran as a 4-chunk plus a 1-remainder
    chunks = {}
    for d in sys_c4.coordinator.dispatch_log:
        if d.model_id.startswith("segment:"):
            for rn in d.nodes:
                chunks.setdefault(rn.uid, []).append(d.segment_steps)
    assert chunks and all(c == [4, 1] for c in chunks.values()), chunks


def test_segment_parity_bitexact_lora():
    wf_inputs = [{"seed": 3, "prompt": "styled"}]
    unfused, _, _ = _serve(make_lora_workflow("sd3", "style"), wf_inputs,
                           steps=5, fused=False, hw=FAST_FETCH)
    fused, _, backend = _serve(make_lora_workflow("sd3", "style"), wf_inputs,
                               steps=5, fused=True, hw=FAST_FETCH)
    np.testing.assert_array_equal(fused[0], unfused[0])
    # the adapter folded into the SEGMENT's params, once
    assert list(backend._folded) == [
        ("segment:backbone:sd3", ("lora:style:sd3",))]


def test_segment_parity_noncfg_family():
    """flux families skip CFG — the scan's non-CFG branch."""
    inputs = [{"seed": 5, "prompt": "probe"}]
    unfused, _, _ = _serve(make_basic_workflow("flux-schnell"), inputs,
                           steps=3, fused=False)
    fused, _, _ = _serve(make_basic_workflow("flux-schnell"), inputs,
                         steps=3, fused=True)
    np.testing.assert_array_equal(fused[0], unfused[0])


# --------------------------------------------------------------------------
# Load-adaptive chunking
# --------------------------------------------------------------------------

def test_choose_segment_steps_policy():
    sched = Scheduler(ProfileStore(GPU_H800))
    # empty queue at low load: take the whole remaining chain
    assert sched.choose_segment_steps(28, n_queued=0) == 28
    # queue pressure: drop to step granularity so arrivals can batch
    assert sched.choose_segment_steps(28, n_queued=3) == 1
    # the signal is queue depth, not inflight count: a saturated fleet
    # whose whole ready set is in this batch still fuses fully
    assert sched.choose_segment_steps(28, n_queued=0, low_load=False) == 28
    # a pending adapter fetch bounds the chunk regardless of load
    assert sched.choose_segment_steps(28, n_queued=0, patches_pending=True) == 1
    fixed = Scheduler(ProfileStore(GPU_H800), segment_chunk=4)
    assert fixed.choose_segment_steps(28, n_queued=0) == 4
    assert fixed.choose_segment_steps(3, n_queued=5) == 3   # clamped


def test_runtime_rechunks_between_segment_completions():
    """segment_chunk=2 over 5 steps: the coordinator re-dispatches the
    SAME node for 2+2+1 steps; every chunk after the first resumes from
    the carried latent."""
    imgs, sys_, _ = _serve(make_basic_workflow("sd3"),
                           [{"seed": 0, "prompt": "x"}], steps=5,
                           fused=True, segment_chunk=2)
    seg = [d for d in sys_.coordinator.dispatch_log
           if d.model_id == "segment:backbone:sd3"]
    assert [d.segment_steps for d in seg] == [2, 2, 1]
    # all three dispatches ran the same request node
    assert len({id(d.nodes[0]) for d in seg}) == 1
    full, _, _ = _serve(make_basic_workflow("sd3"),
                        [{"seed": 0, "prompt": "x"}], steps=5, fused=True)
    np.testing.assert_array_equal(imgs[0], full[0])


def test_segment_profile_scales_with_steps():
    ms = ModelSet(FAMILIES["sd3"])
    seg = ms.backbone.build_segment([], 28)
    profiles = ProfileStore(GPU_H800)
    p = profiles.profile_model(seg)
    one = p.infer_time(1, 1, steps=1)
    full = p.infer_time(1, 1)              # defaults to steps_per_call=28
    # 28 steps of work, but the fixed dispatch overhead is paid once
    per_step = one - GPU_H800.dispatch_overhead
    assert full == pytest.approx(
        28 * per_step + GPU_H800.dispatch_overhead, rel=1e-9)
    assert seg.cost().param_bytes == ms.backbone.cost().param_bytes


def test_segment_batches_mixed_progress():
    """Two requests whose segments are at different schedule offsets can
    still stack into one scan (per-item t columns)."""
    ms = ModelSet(FAMILIES["sd3"])
    seg = ms.backbone.build_segment([], 4)
    comps = seg.load()
    cfg = FAMILIES["sd3"].dit
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    sched = [1.0, 0.75, 0.5, 0.25, 0.0]
    kws = []
    for i, start in enumerate((0, 2)):
        kws.append({
            "latents": jax.random.normal(
                ks[2 * i], (1, cfg.latent_size, cfg.latent_size,
                            cfg.latent_channels)),
            "prompt_embeds": jax.random.normal(
                ks[2 * i + 1], (1, cfg.text_tokens, cfg.text_dim)),
            "t_mid": tuple(sched[:4]), "t_cur": tuple(sched[:4]),
            "t_next": tuple(sched[1:]), "guidance": 4.5,
            "_seg_start": start, "_seg_steps": 2,
        })
    batched = seg.execute_batch(comps, [dict(k) for k in kws])
    solo = [seg.execute(comps, **dict(k)) for k in kws]
    for got, want in zip(batched, solo):
        np.testing.assert_array_equal(np.asarray(got["latents"]),
                                      np.asarray(want["latents"]))


# --------------------------------------------------------------------------
# Sharded execution (runs in the CI mesh job; skipped on 1-device hosts)
# --------------------------------------------------------------------------

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (CI mesh job forces 8 virtual CPU devices)")


@multi_device
@pytest.mark.parametrize("n_cns", [0, 1])
def test_segment_sharded_parity_k2(n_cns):
    """One SPMD scan over a 2-device submesh (CFG branches on separate
    devices every step) matches the single-device scan."""
    from repro.core import MeshManager, ShardedBackend

    fam = FAMILIES["sd3"]
    cfg = fam.dit
    ms = ModelSet(fam)
    seg = ms.backbone.build_segment([ms.cn1][:n_cns], 3)
    mm = MeshManager()
    backend = ShardedBackend(mm)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    sched = [1.0, 2 / 3, 1 / 3, 0.0]
    kw = {
        "latents": jax.random.normal(
            ks[0], (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)),
        "prompt_embeds": jax.random.normal(
            ks[1], (1, cfg.text_tokens, cfg.text_dim)),
        "t_mid": tuple(sched[:3]), "t_cur": tuple(sched[:3]),
        "t_next": tuple(sched[1:]), "guidance": 4.0,
    }
    if n_cns:
        kw["cond_latents"] = jax.random.normal(
            ks[2], (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels))
    ref, _, _ = backend.execute_batch(seg, [dict(kw)])
    out, _, _ = backend.execute_batch(seg, [dict(kw)],
                                      mesh=mm.submesh([0, 1]))
    np.testing.assert_allclose(np.asarray(out[0]["latents"]),
                               np.asarray(ref[0]["latents"]),
                               atol=1e-5, rtol=1e-5)
    assert backend.shard_log[-1][0] == seg.model_id
    assert backend.shard_log[-1][2] == 2
