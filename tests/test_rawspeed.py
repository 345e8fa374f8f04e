"""Raw-speed plane: donated scan buffers and denoise/decode overlap.

* donation (``REPRO_DONATE``): the fused segment scan donates its latent
  carry — XLA aliases input to output, so the buffer really dies after
  the call; the first chunk copies the engine-held input (the datastore's
  value must survive for recovery/other consumers); outputs stay
  bit-exact with donation off;
* overlap (``REPRO_OVERLAP``): the coordinator dispatches VAE decode of
  batch N onto an executor still running batch N+1's denoise segment —
  the decode's priced cost drops to its EXPOSED (non-hidden) part, the
  virtual makespan shrinks, at most one overlap rides per segment
  window, and outputs stay bit-identical to the overlap-off run on the
  executable plane.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import LocalBackend, Scheduler, ServingSystem
from repro.core.runtime import overlap_enabled, set_overlap
from repro.diffusion import FAMILIES, make_basic_workflow
from repro.diffusion.ops import DenoiseSegment, DiffusionBackbone, VAEDecode
from repro.diffusion.sampler import donate_buffers_enabled, set_donate_buffers

KEY = jax.random.PRNGKey(3)


# --------------------------------------------------------------------------
# donation: buffer death, copy-on-first-chunk guard, bit-exact parity
# --------------------------------------------------------------------------

def _segment(steps=3):
    return DenoiseSegment(DiffusionBackbone(FAMILIES["sd3"]), [], steps)


def _seg_kwargs(seg, b=2):
    cfg = seg.family.dit
    ks = jax.random.split(KEY, 2)
    lat = jax.random.normal(
        ks[0], (b, cfg.latent_size, cfg.latent_size, cfg.latent_channels))
    emb = jax.random.normal(ks[1], (b, cfg.text_tokens, cfg.text_dim))
    s = seg.n_steps
    grid = np.linspace(1.0, 0.0, s + 1)
    return {
        "latents": lat, "prompt_embeds": emb,
        "t_mid": tuple((grid[:-1] + grid[1:]) / 2),
        "t_cur": tuple(grid[:-1]), "t_next": tuple(grid[1:]),
        "guidance": 4.5,
    }


def test_donated_scan_deletes_carry_buffer():
    """donate_argnums really threads through: the carry argument is DEAD
    after the jitted scan (XLA aliased it to the output)."""
    seg = _segment()
    prev = set_donate_buffers(True)
    try:
        comps = seg.load()
        assert comps["donate"]
        kw = _seg_kwargs(seg)
        carry = jnp.copy(kw["latents"])
        out = comps["scan"](
            comps["backbone"]["params"], (), carry, kw["prompt_embeds"],
            jnp.zeros((0,)),
            *_stacked_schedule(seg, kw), jnp.full((2,), 4.5))
        assert carry.is_deleted()
        assert not out.is_deleted()
    finally:
        set_donate_buffers(prev)


def test_donation_off_keeps_carry_alive():
    seg = _segment()
    prev = set_donate_buffers(False)
    try:
        comps = seg.load()
        assert not comps.get("donate")
        kw = _seg_kwargs(seg)
        carry = kw["latents"]
        comps["scan"](
            comps["backbone"]["params"], (), carry, kw["prompt_embeds"],
            jnp.zeros((0,)),
            *_stacked_schedule(seg, kw), jnp.full((2,), 4.5))
        assert not carry.is_deleted()
        np.asarray(carry)            # still readable
    finally:
        set_donate_buffers(prev)


def _stacked_schedule(seg, kw):
    b = int(kw["latents"].shape[0])
    cols = []
    for name in ("t_mid", "t_cur", "t_next"):
        sl = np.asarray(kw[name], np.float32)
        cols.append(jnp.asarray(np.repeat(sl[:, None], b, axis=1)))
    return tuple(cols)


def test_first_chunk_copy_guard_preserves_datastore_value():
    """``execute`` with donation on must never kill the caller's buffer:
    the engine (and chaos replay) may still read it — only the private
    copy is donated."""
    seg = _segment()
    prev = set_donate_buffers(True)
    try:
        comps = seg.load()
        kw = _seg_kwargs(seg)
        held = kw["latents"]
        before = np.asarray(held).copy()
        out = seg.execute(comps, **kw)
        assert not held.is_deleted()
        np.testing.assert_array_equal(np.asarray(held), before)
        assert out["latents"].shape == held.shape
    finally:
        set_donate_buffers(prev)


def test_donation_parity_bitexact():
    """Aliasing is an allocation optimization, not an arithmetic one."""
    seg = _segment()
    kw = _seg_kwargs(seg)

    def run(flag):
        prev = set_donate_buffers(flag)
        try:
            # fresh components per arm: the scan bakes donation at jit time
            comps = _segment().load()
            return np.asarray(seg.execute(comps, **dict(kw))["latents"])
        finally:
            set_donate_buffers(prev)

    np.testing.assert_array_equal(run(False), run(True))


def test_donate_flag_roundtrip():
    prev = set_donate_buffers(True)
    try:
        assert donate_buffers_enabled()
        assert set_donate_buffers(False) is True
        assert not donate_buffers_enabled()
    finally:
        set_donate_buffers(prev)


# --------------------------------------------------------------------------
# overlap: sim-plane determinism (virtual timeline, no measurement noise)
# --------------------------------------------------------------------------

def _sim_arm(overlap, n=6, steps=6):
    s = ServingSystem(n_executors=1, overlap=overlap)
    s.coordinator.scheduler = Scheduler(
        s.profiles, use_declared_max_batch=True, max_batch_cap=1,
        segment_chunk=steps)
    wf = make_basic_workflow("sd3")
    s.register(wf)
    reqs = [s.submit(wf.name, inputs={"seed": i, "prompt": f"p{i}"},
                     arrival=0.0, steps=steps) for i in range(n)]
    s.run()
    assert all(r.status == "done" for r in reqs)
    return s


def test_overlap_shrinks_sim_makespan():
    off = _sim_arm(overlap=False)
    on = _sim_arm(overlap=True)
    assert off.coordinator.n_overlap_dispatches == 0
    assert on.coordinator.n_overlap_dispatches > 0
    assert on.coordinator.overlap_hidden_seconds > 0
    assert on.coordinator.now < off.coordinator.now
    # same work completed either way
    assert len(on.coordinator.finished) == len(off.coordinator.finished)


def test_overlap_one_slot_per_segment_window():
    on = _sim_arm(overlap=True)
    co = on.coordinator
    n_segments = sum(1 for b in co.dispatch_log
                     if b.model_id.startswith("segment:"))
    assert 0 < co.n_overlap_dispatches <= n_segments


def test_overlap_records_windowed_batches():
    on = _sim_arm(overlap=True)
    windowed = [b for b in on.coordinator.dispatch_log
                if b.overlap_window > 0]
    assert len(windowed) == on.coordinator.n_overlap_dispatches
    assert all(b.model_id.startswith("vae:") for b in windowed)
    assert all(b.batch_size == 1 for b in windowed)   # overlap rides k=1


def test_overlappable_is_declared_on_vae_only():
    assert VAEDecode(FAMILIES["sd3"]).overlappable
    assert not getattr(_segment(), "overlappable", False)
    assert not getattr(DiffusionBackbone(FAMILIES["sd3"]), "overlappable",
                       False)


def test_overlap_flag_roundtrip():
    prev = set_overlap(True)
    try:
        assert overlap_enabled()
        assert set_overlap(False) is True
        assert not overlap_enabled()
    finally:
        set_overlap(prev)


# --------------------------------------------------------------------------
# overlap: executable-plane parity (real forwards, virtual timeline)
# --------------------------------------------------------------------------

def _real_arm(overlap, n=4, steps=4):
    be = LocalBackend()
    s = ServingSystem(n_executors=1, backend=be, overlap=overlap)
    s.coordinator.scheduler = Scheduler(
        s.profiles, use_declared_max_batch=True, max_batch_cap=1,
        segment_chunk=steps)
    wf = make_basic_workflow("sd3")
    s.register(wf)
    reqs = [s.submit(wf.name, inputs={"seed": i, "prompt": f"p{i}"},
                     arrival=0.0, steps=steps) for i in range(n)]
    s.run()
    assert all(r.status == "done" for r in reqs)
    imgs = [np.asarray(s.coordinator.engine.value_of(
        r.ref_key(r.graph.outputs["image"]))) for r in reqs]
    return s, imgs


def test_overlap_executable_plane_bitexact():
    """Overlap reorders the virtual timeline, never the arithmetic: the
    served images match the overlap-off run bit for bit, and the hidden
    decode really dispatched while a segment occupied the executor."""
    _, want = _real_arm(overlap=False)
    on, got = _real_arm(overlap=True)
    assert on.coordinator.n_overlap_dispatches > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
