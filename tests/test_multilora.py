"""Multi-tenant LoRA serving: grouped-kernel parity and system gates.

Three layers of coverage for the unfolded batched multi-adapter route:

* kernel: ``lora_apply`` / ``lora_apply_grouped`` against the pure-jnp
  oracles over non-tile-divisible shapes, ranks 1..64, scales, and the
  ``use_kernel=False`` fallback — a hypothesis property sweep when the
  optional dependency is installed, plus a deterministic edge-case grid
  that always runs (including the padding edge where ``min(block_m, m)``
  shrinks the tile);
* backend state: :class:`AdapterPool` LRU accounting and the bounded
  ``LocalBackend._folded`` fold cache (eviction counters + forward_log
  markers);
* system: cross-tenant batches formed by the multilora scheduler match
  the folded solo reference per request on the single-device, mesh and
  proc planes (the parity gate: <= 2e-4, bit-exact for unpatched rows).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    GraphCompiler,
    LocalBackend,
    ProcBackend,
    Scheduler,
    ServingSystem,
    ShardedBackend,
    processes_available,
)
from repro.core.executor import AdapterPool
from repro.core.telemetry import FoldCacheEviction, default_registry
from repro.core.passes import InlineTrivialPass, JitCompilePass, SegmentFusionPass
from repro.core.registry import WorkflowRegistry
from repro.diffusion import FAMILIES, ModelSet, make_basic_workflow, make_lora_workflow
from repro.kernels.lora_matmul.ops import lora_apply, lora_apply_grouped
from repro.kernels.lora_matmul.ref import lora_matmul_grouped_ref, lora_matmul_ref

KEY = jax.random.PRNGKey(7)


# --------------------------------------------------------------------------
# kernel parity: deterministic edge grid (always runs)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,r", [
    (1, 8, 8, 1),          # single row, rank-1: every tile shrinks
    (5, 24, 40, 3),        # nothing tile-divisible
    (33, 128, 96, 8),      # m just past one block
    (128, 100, 200, 64),   # max rank, ragged K
])
def test_lora_apply_edge_shapes(m, k, n, r):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
    a = jax.random.normal(ks[2], (k, r)) / np.sqrt(k)
    b = jax.random.normal(ks[3], (r, n))
    ref = lora_matmul_ref(x, w, a, b, scale=1.3)
    out = lora_apply(x, w, a, b, scale=1.3, block_m=32, block_n=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # the no-kernel fallback is the oracle itself (modulo jit fusion ULPs)
    np.testing.assert_allclose(
        np.asarray(lora_apply(x, w, a, b, scale=1.3, use_kernel=False)),
        np.asarray(ref), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("g,r", [(1, 4), (3, 8), (4, 1)])
def test_lora_apply_grouped_matches_per_adapter_fold(g, r):
    """Grouped rows match the corresponding single-adapter ``lora_apply``;
    rows with idx=-1 match the plain projection bit-exactly (jnp route)."""
    m, k, n = 11, 48, 56
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
    a = jax.random.normal(ks[2], (g, k, r)) / np.sqrt(k)
    b = jax.random.normal(ks[3], (g, r, n))
    scales = jnp.asarray([0.5 + 0.25 * i for i in range(g)])
    idx = jnp.asarray([(i % (g + 1)) - 1 for i in range(m)], jnp.int32)

    out = lora_apply_grouped(x, w, a, b, idx, scales, use_kernel=False)
    base = np.asarray(x @ w)
    for i in range(m):
        gi = int(idx[i])
        if gi < 0:
            np.testing.assert_array_equal(np.asarray(out)[i], base[i])
        else:
            want = lora_matmul_ref(x[i:i + 1], w, a[gi], b[gi],
                                   scale=float(scales[gi]))
            np.testing.assert_allclose(np.asarray(out)[i],
                                       np.asarray(want)[0],
                                       atol=1e-5, rtol=1e-5)
    # kernel route (mask-trick grouped matmul) vs the grouped oracle
    outk = lora_apply_grouped(x, w, a, b, idx, scales, use_kernel=True,
                              block_m=32, block_n=32, block_k=32)
    np.testing.assert_allclose(np.asarray(outk), np.asarray(out),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# kernel parity: hypothesis property sweep (optional dependency)
# --------------------------------------------------------------------------

try:
    import os

    from hypothesis import HealthCheck, given, settings, strategies as st

    settings.register_profile("ml-ci", max_examples=25, deadline=None,
                              derandomize=True, print_blob=True)
    settings.register_profile("ml-dev", max_examples=10, deadline=None,
                              suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile(
        "ml-ci" if os.environ.get("HYPOTHESIS_PROFILE") == "ci" else "ml-dev")

    @given(m=st.integers(1, 80), k=st.integers(1, 64), n=st.integers(1, 64),
           r=st.integers(1, 64), scale=st.floats(0.0, 2.0),
           block=st.sampled_from([8, 32, 128]), seed=st.integers(0, 2**16))
    def test_lora_apply_property(m, k, n, r, scale, block, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        x = jax.random.normal(ks[0], (m, k))
        w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
        a = jax.random.normal(ks[2], (k, r)) / np.sqrt(k)
        b = jax.random.normal(ks[3], (r, n))
        ref = lora_matmul_ref(x, w, a, b, scale=scale)
        out = lora_apply(x, w, a, b, scale=scale,
                         block_m=block, block_n=block, block_k=block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(
            np.asarray(lora_apply(x, w, a, b, scale=scale, use_kernel=False)),
            np.asarray(ref), atol=1e-6, rtol=1e-6)

    @given(m=st.integers(1, 48), k=st.integers(1, 64), n=st.integers(1, 64),
           g=st.integers(1, 5), r=st.integers(1, 32),
           seed=st.integers(0, 2**16))
    def test_lora_apply_grouped_property(m, k, n, g, r, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        x = jax.random.normal(ks[0], (m, k))
        w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
        a = jax.random.normal(ks[2], (g, k, r)) / np.sqrt(k)
        b = jax.random.normal(ks[3], (g, r, n))
        scales = jax.random.uniform(ks[4], (g,), minval=0.1, maxval=2.0)
        idx = jnp.asarray(
            np.random.default_rng(seed).integers(-1, g, size=m), jnp.int32)
        ref = lora_matmul_grouped_ref(x, w, a, b, idx, scales)
        out = lora_apply_grouped(x, w, a, b, idx, scales, use_kernel=True,
                                 block_m=32, block_n=32, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

except ImportError:
    @pytest.mark.skip(reason="property sweep needs the optional hypothesis dependency")
    def test_lora_apply_property():
        pass

    @pytest.mark.skip(reason="property sweep needs the optional hypothesis dependency")
    def test_lora_apply_grouped_property():
        pass


# --------------------------------------------------------------------------
# AdapterPool: LRU accounting
# --------------------------------------------------------------------------

class _StubPatch:
    def __init__(self, mid, kb=1):
        self.model_id = mid
        self._kb = kb
        self.loads = 0

    def load(self, device=None):
        self.loads += 1
        return {"a": np.zeros(self._kb * 256, np.float32)}  # kb KiB


def test_adapter_pool_lru_eviction_and_counters():
    pool = AdapterPool(capacity_bytes=2.5 * 1024)
    pa, pb, pc = _StubPatch("a"), _StubPatch("b"), _StubPatch("c")
    pool.get(pa)
    pool.get(pb)
    assert pool.misses == 2 and pool.evictions == 0
    pool.get(pa)                      # refresh: a is now most-recent
    assert pool.hits == 1
    pool.get(pc)                      # over budget -> evict LRU = b
    assert pool.evictions == 1
    assert pool.ids() == ["a", "c"]
    assert pool.resident_bytes <= 2.5 * 1024
    _, dt = pool.get(pb)              # re-load after eviction
    assert pb.loads == 2 and dt >= 0
    assert "b" in pool and "a" not in pool  # a was LRU at that point


def test_adapter_pool_never_evicts_below_one_entry():
    pool = AdapterPool(capacity_bytes=1)      # smaller than any entry
    big = _StubPatch("big", kb=4)
    comps, _ = pool.get(big)
    assert pool.ids() == ["big"]              # resident despite overflow
    again, _ = pool.get(big)
    assert again is comps and pool.hits == 1


def test_adapter_pool_seed_is_idempotent():
    pool = AdapterPool(capacity_bytes=1 << 20)
    comps = {"a": np.ones(8, np.float32)}
    pool.seed("x", comps)
    pool.seed("x", {"a": np.zeros(8, np.float32)})   # no overwrite
    np.testing.assert_array_equal(pool.get(_StubPatch("x"))[0]["a"],
                                  np.ones(8, np.float32))


# --------------------------------------------------------------------------
# bounded fold cache on LocalBackend
# --------------------------------------------------------------------------

class _StubModel:
    def __init__(self, mid):
        self.model_id = mid

    def load(self, device=None):
        return {"w": np.zeros(256, np.float32)}     # 1 KiB

    def fold_patches(self, comps, patches, patch_comps):
        return {"w": comps["w"] + len(patches)}


def test_fold_cache_lru_eviction_markers():
    reg = default_registry()
    before = len(reg.events_of(FoldCacheEviction))
    be = LocalBackend(folded_budget_bytes=2.5 * 1024)
    base = _StubModel("base")
    folds = [[_StubPatch(f"p{i}")] for i in range(3)]
    be.components_for(base, folds[0])
    be.components_for(base, folds[1])
    assert be.folded_evictions == 0
    be.components_for(base, folds[0])           # refresh placement 0
    be.components_for(base, folds[2])           # evicts placement 1 (LRU)
    assert be.folded_evictions == 1
    evs = reg.events_of(FoldCacheEviction)[before:]
    assert [(e.model_id, e.patch_ids) for e in evs] == [("base", ("p1",))]
    assert list(be._folded) == [("base", ("p0",)), ("base", ("p2",))]
    assert be.folded_resident_bytes <= 2.5 * 1024


# --------------------------------------------------------------------------
# system parity gates: grouped multi-LoRA == folded solo, per request
# --------------------------------------------------------------------------

SUBS = [("sd3:lora:tenantA", 3), ("sd3:lora:tenantB", 3), ("sd3:basic", 3)]
PARITY_TOL = 2e-4


def _build_system(backend, multilora, fused=True):
    """Serving system with deterministic patch semantics: AsyncLoRAPass is
    stripped so adapters resolve at dispatch in both solo and mixed runs
    (its fold-in step depends on measured wall seconds)."""
    s = ServingSystem(n_executors=1, backend=backend)
    passes = ([InlineTrivialPass()]
              + ([SegmentFusionPass()] if fused else [])
              + [JitCompilePass()])
    s.registry = WorkflowRegistry(GraphCompiler(passes))
    s.coordinator.scheduler = Scheduler(
        s.profiles, use_declared_max_batch=True, multilora=multilora)
    ms = ModelSet(FAMILIES["sd3"])
    for wf in (make_basic_workflow("sd3", ms),
               make_lora_workflow("sd3", "tenantA", ms),
               make_lora_workflow("sd3", "tenantB", ms)):
        s.register(wf)
    return s


def _image(s, r):
    return np.asarray(s.coordinator.engine.value_of(
        r.ref_key(r.graph.outputs["image"])))


def _run_mixed(s):
    reqs = [s.submit(n, inputs={"seed": sd, "prompt": "parity probe"},
                     arrival=0.0, steps=3) for n, sd in SUBS]
    s.run()
    for (n, _), r in zip(SUBS, reqs):
        assert r.status == "done", (n, r.status)
    return reqs


@pytest.fixture(scope="module")
def folded_refs():
    """Per-workflow solo runs on the legacy fold path (multilora off)."""
    refs = {}
    for name, seed in SUBS:
        be = LocalBackend()
        s = _build_system(be, multilora=False)
        r = s.submit(name, inputs={"seed": seed, "prompt": "parity probe"},
                     steps=3)
        s.run()
        assert r.status == "done"
        assert be.multilora_forwards == 0, "solo traffic must keep the fold path"
        refs[name] = _image(s, r)
    return refs


@pytest.mark.parametrize("fused", [True, False], ids=["segment", "per-step"])
def test_multilora_parity_single_device(folded_refs, fused):
    be = LocalBackend()
    s = _build_system(be, multilora=True, fused=fused)
    reqs = _run_mixed(s)
    ml = [b for b in s.coordinator.dispatch_log if b.multilora]
    assert ml, "cross-tenant traffic must form multilora batches"
    assert be.multilora_forwards > 0
    # grouped batches never mutate the executor's folded patch state
    for ex in s.executors:
        for mid, ps in ex.patch_state.items():
            assert not ps, (mid, ps)
    for (n, _), r in zip(SUBS, reqs):
        d = np.abs(_image(s, r) - folded_refs[n]).max()
        assert d <= PARITY_TOL, (n, d)
    # unpatched requests riding a mixed batch stay bit-exact
    np.testing.assert_array_equal(_image(s, reqs[2]), folded_refs["sd3:basic"])
    # adapters actually distinguish tenants
    assert np.abs(_image(s, reqs[0]) - _image(s, reqs[1])).max() > 1e-6


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (CI mesh job forces 8)")
def test_multilora_parity_mesh(folded_refs):
    be = ShardedBackend()
    s = _build_system(be, multilora=True)
    reqs = _run_mixed(s)
    assert any(b.multilora for b in s.coordinator.dispatch_log)
    assert be.multilora_forwards > 0
    for (n, _), r in zip(SUBS, reqs):
        d = np.abs(_image(s, r) - folded_refs[n]).max()
        assert d <= PARITY_TOL, (n, d)


@pytest.mark.skipif(not processes_available(),
                    reason="sandboxed runner: cannot spawn worker processes")
def test_multilora_parity_proc(folded_refs):
    be = ProcBackend()
    s = _build_system(be, multilora=True)
    with s:
        reqs = _run_mixed(s)
        assert any(b.multilora for b in s.coordinator.dispatch_log)
        # both tenants' decoded factors shipped exactly once
        assert be.adapter_ships == 2 and be.adapter_hits == 0
        for (n, _), r in zip(SUBS, reqs):
            d = np.abs(_image(s, r) - folded_refs[n]).max()
            assert d <= PARITY_TOL, (n, d)
        # a warm second wave rides bare staged refs, nothing re-ships
        _run_mixed(s)
        assert be.adapter_ships == 2 and be.adapter_hits >= 2
