"""Quantized backbone forwards (``REPRO_QUANT``): kernel parity, the
quantized-factor AdapterPool accounting, and end-to-end serving gates.

Four layers of coverage for the raw-speed quant plane:

* kernel: ``quant_apply`` (Pallas w8a8 int8 matmul in interpret mode off
  TPU) against the int32-accumulating jnp oracle — exact — and against
  the fp32 dense projection — bounded quantization error;
* representation: quantize/dequantize roundtrip error and the ~4x
  param-byte shrink the QuantizedParams side-structure buys;
* backend state: the AdapterPool's byte accounting sees quantized factor
  sizes, its hit/miss counters stay coherent when ``REPRO_QUANT`` flips
  mid-run, and the proc plane's adapter ship payload carries the small
  int8 form;
* system parity: denoised latents under int8 stay within 2e-2 relative
  of the fp32 path (fp8 is weight-only storage — looser, 5e-2), and the
  served image output stays within the documented image-space envelope
  on the single-device, mesh and proc planes.
"""

import contextlib
import os
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    LocalBackend,
    ProcBackend,
    ServingSystem,
    ShardedBackend,
    processes_available,
)
from repro.core.executor import AdapterPool
from repro.diffusion import FAMILIES, LoRAAdapter, make_basic_workflow
from repro.diffusion.mmdit import init_mmdit, mmdit_apply, quantize_mmdit_params
from repro.diffusion.sampler import denoise_step, flow_schedule
from repro.kernels.quant_matmul.ops import (
    dequantize_weight,
    is_quantized,
    quant_apply,
    quantize_weight,
)
from repro.kernels.quant_matmul.ref import quant_matmul_ref
from repro.nn.layers import quant_mode, set_quant_mode

KEY = jax.random.PRNGKey(11)

# int8 is w8a8 (both operands quantized); fp8 is weight-only storage with
# a full-precision matmul, so its END-TO-END error is larger (no
# activation rounding, but e4m3 mantissa is coarser than int8 on the
# weight tensor).  Latent gates per ISSUE; image gates are the measured
# envelope after VAE decode (decode amplifies relative error ~1.4x).
LATENT_TOL = {"int8": 2e-2, "fp8": 5e-2}
IMAGE_TOL = {"int8": 3e-2, "fp8": 8e-2}


@contextlib.contextmanager
def _quant(mode):
    prev = set_quant_mode(mode)
    try:
        yield
    finally:
        set_quant_mode(prev)


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


# --------------------------------------------------------------------------
# kernel parity: Pallas int8 path vs jnp oracle vs fp32 dense
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (1, 8, 8),             # single row: every tile shrinks
    (5, 24, 40),           # nothing tile-divisible
    (33, 128, 96),         # m just past one block
    (128, 100, 200),       # ragged K, wide N
])
def test_quant_apply_int8_kernel_matches_oracle(m, k, n):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
    q = quantize_weight(w, "int8")
    want = quant_apply(x, q["qw"], q["qs"], use_kernel=False)
    got = quant_apply(x, q["qw"], q["qs"], use_kernel=True,
                      block_m=32, block_n=32, block_k=32)
    # same int32 accumulation, same scales: bit-identical up to jit fusion
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quant_apply_close_to_dense(mode):
    m, k, n = 16, 64, 48
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) / np.sqrt(k)
    q = quantize_weight(w, mode)
    got = np.asarray(quant_apply(x, q["qw"], q["qs"], use_kernel=False))
    want = np.asarray(x @ w)
    assert _rel(got, want) <= (2e-2 if mode == "int8" else 4e-2)


def test_quant_matmul_ref_is_int32_accumulating():
    """The oracle accumulates in int32 — saturating int8 products would
    diverge; max-magnitude inputs exercise the accumulator width."""
    m, k, n = 4, 256, 8
    xq = jnp.full((m, k), 127, jnp.int8)
    wq = jnp.full((k, n), 127, jnp.int8)
    xs = jnp.ones((m, 1), jnp.float32)
    ws = jnp.ones((1, n), jnp.float32)
    out = np.asarray(quant_matmul_ref(xq, wq, xs, ws))
    np.testing.assert_array_equal(out, np.full((m, n), 127.0 * 127.0 * k))


# --------------------------------------------------------------------------
# representation: roundtrip error, byte shrink
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantize_roundtrip_and_shrink(mode):
    w = jax.random.normal(KEY, (2, 64, 48)) / 8.0   # layer-stacked
    q = quantize_weight(w, mode)
    assert is_quantized(q)
    assert q["qw"].dtype == (jnp.int8 if mode == "int8"
                             else jnp.float8_e4m3fn)
    back = dequantize_weight(q)
    # int8: 8-bit symmetric grid; fp8 e4m3: 3 mantissa bits (~2^-3 rel)
    assert _rel(back, w) <= (1e-2 if mode == "int8" else 4e-2)
    # the whole point: ~4x smaller residency (scales are per-channel)
    qbytes = q["qw"].nbytes + q["qs"].nbytes
    assert qbytes < 0.3 * w.astype(jnp.float32).nbytes
    # quantizing twice is the identity (quantize-on-fold re-entrancy)
    assert quantize_weight(q, mode) is q


def test_quantize_mmdit_params_shrinks_stream_weights():
    cfg = FAMILIES["sd3"].dit
    params = init_mmdit(KEY, cfg)
    with _quant("int8"):
        qparams = quantize_mmdit_params(params)
    fp32 = sum(l.nbytes for l in jax.tree.leaves(params))
    qb = sum(l.nbytes for l in jax.tree.leaves(qparams))
    assert qb < 0.6 * fp32          # toy config: embeds are a big fraction
    assert is_quantized(qparams["layers"]["img"]["wq"])
    # embeds / head stay fp32 (tiny, I/O-critical)
    assert not is_quantized(qparams["patch_embed"])


# --------------------------------------------------------------------------
# AdapterPool: quantized factor accounting
# --------------------------------------------------------------------------

def _adapter(name="styleq"):
    return LoRAAdapter(FAMILIES["sd3"], name)


def test_adapter_pool_bytes_use_quantized_sizes():
    with _quant("off"):
        pool = AdapterPool(capacity_bytes=1 << 30)
        pool.get(_adapter())
        fp32_bytes = pool.resident_bytes
    with _quant("int8"):
        pool = AdapterPool(capacity_bytes=1 << 30)
        comps, _ = pool.get(_adapter())
        q_bytes = pool.resident_bytes
    # the pool's budget sees the int8 leaves, not a dequantized shadow
    assert q_bytes < 0.5 * fp32_bytes
    for t in ("wq", "wk", "wv", "wo"):
        q = comps["lora"][f"{t}_a"]
        assert is_quantized(q) and q["qw"].dtype == jnp.int8


def test_adapter_pool_counters_coherent_across_quant_flip():
    """Flipping REPRO_QUANT mid-run never corrupts the pool: a resident
    entry stays a hit (stale-but-consistent representation), and only an
    explicit drop reloads it in the new mode with new byte accounting."""
    pool = AdapterPool(capacity_bytes=1 << 30)
    with _quant("off"):
        comps_off, dt = pool.get(_adapter())
        assert (pool.misses, pool.hits) == (1, 0) and dt > 0
        bytes_off = pool.resident_bytes
    with _quant("int8"):
        again, dt = pool.get(_adapter())
        # keyed by model_id: the flip alone must not thrash the pool
        assert again is comps_off and dt == 0.0
        assert (pool.misses, pool.hits) == (1, 1)
        assert pool.resident_bytes == bytes_off
        pool.drop(_adapter().model_id)
        assert pool.resident_bytes == 0
        comps_q, _ = pool.get(_adapter())
        assert (pool.misses, pool.hits) == (2, 1)
        assert pool.resident_bytes < 0.5 * bytes_off
        assert is_quantized(comps_q["lora"]["wq_a"])


def test_adapter_ship_payload_is_quantized():
    """The proc plane ships exactly what the supervisor-side pool holds
    (``adapter_pool.get(p)`` -> pickle): under int8 the wire payload is
    the small form."""
    with _quant("off"):
        comps = AdapterPool(1 << 30).get(_adapter())[0]
        wire_off = len(pickle.dumps(comps))
    with _quant("int8"):
        comps = AdapterPool(1 << 30).get(_adapter())[0]
        wire_q = len(pickle.dumps(comps))
        assert is_quantized(comps["lora"]["wq_a"])
    assert wire_q < 0.5 * wire_off


# --------------------------------------------------------------------------
# analytic pricing: the roofline sees the quant mode
# --------------------------------------------------------------------------

def test_profile_prices_quantized_forwards():
    """Quantizable models get the modeled MXU/residency win (int8: 2x
    issue rate + halved weight stream; fp8: residency only); VAEs price
    identically in every mode."""
    from repro.core import GPU_H800, ProfileStore
    from repro.diffusion.ops import DiffusionBackbone, VAEDecode

    store = ProfileStore(GPU_H800)
    bb = store.profile_model(DiffusionBackbone(FAMILIES["sd3"]))
    vae = store.profile_model(VAEDecode(FAMILIES["sd3"]))
    with _quant("off"):
        t_off, v_off = bb.infer_time(1), vae.infer_time(1)
        load_off, pb_off = bb.load_time(), bb.param_bytes
    with _quant("int8"):
        assert bb.infer_time(1) < 0.75 * t_off
        assert vae.infer_time(1) == v_off
        assert bb.load_time() < load_off
        assert bb.param_bytes == 0.5 * pb_off
    with _quant("fp8"):
        t_fp8 = bb.infer_time(1)
        assert t_fp8 <= t_off                  # halved weight stream
        assert bb.param_bytes == 0.5 * pb_off


# --------------------------------------------------------------------------
# system parity: denoised latents (module-level) and served images
# --------------------------------------------------------------------------

def _denoised_latents(params, cfg, steps=4):
    ks = jax.random.split(KEY, 2)
    b = 2
    lat = jax.random.normal(
        ks[0], (b, cfg.latent_size, cfg.latent_size, cfg.latent_channels))
    text = jax.random.normal(ks[1], (b, cfg.text_tokens, cfg.text_dim))
    ts = flow_schedule(steps)
    for i in range(steps):
        t = jnp.full((b,), ts[i])
        v = mmdit_apply(params, cfg, lat, t, text)
        lat = denoise_step(lat, v, ts[i], ts[i + 1])
    return np.asarray(lat)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_denoised_latent_parity(mode):
    """The ISSUE gate: quantized multi-step denoise stays within
    LATENT_TOL relative of the fp32 trajectory (errors compound across
    steps — this is the honest end-of-chain number, not one matmul)."""
    cfg = FAMILIES["sd3"].dit
    params = init_mmdit(KEY, cfg)
    want = _denoised_latents(params, cfg)
    with _quant(mode):
        qparams = quantize_mmdit_params(params)
    got = _denoised_latents(qparams, cfg)
    assert _rel(got, want) <= LATENT_TOL[mode], _rel(got, want)


def _serve_images(backend, steps=4, n=2):
    s = ServingSystem(n_executors=1, backend=backend)
    wf = make_basic_workflow("sd3")
    s.register(wf)
    reqs = [s.submit(wf.name, inputs={"seed": i, "prompt": f"p{i}"},
                     arrival=0.0, steps=steps) for i in range(n)]
    s.run()
    assert all(r.status == "done" for r in reqs)
    return [np.asarray(s.coordinator.engine.value_of(
        r.ref_key(r.graph.outputs["image"]))) for r in reqs]


@pytest.fixture(scope="module")
def fp32_images():
    with _quant("off"):
        return _serve_images(LocalBackend())


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_served_image_parity_single_device(fp32_images, mode):
    with _quant(mode):
        got = _serve_images(LocalBackend())
    for a, b in zip(got, fp32_images):
        assert _rel(a, b) <= IMAGE_TOL[mode], _rel(a, b)
        assert _rel(a, b) > 0          # quant really engaged


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (CI mesh job forces 8)")
def test_served_image_parity_mesh(fp32_images):
    with _quant("int8"):
        got = _serve_images(ShardedBackend())
    for a, b in zip(got, fp32_images):
        assert _rel(a, b) <= IMAGE_TOL["int8"], _rel(a, b)


@pytest.mark.skipif(not processes_available(),
                    reason="sandboxed runner: cannot spawn worker processes")
def test_served_image_parity_proc(fp32_images, monkeypatch):
    # workers read REPRO_QUANT from the inherited environment at import
    monkeypatch.setenv("REPRO_QUANT", "int8")
    with _quant("int8"):
        be = ProcBackend()
        s = ServingSystem(n_executors=1, backend=be)
        wf = make_basic_workflow("sd3")
        s.register(wf)
        with s:
            reqs = [s.submit(wf.name, inputs={"seed": i, "prompt": f"p{i}"},
                             arrival=0.0, steps=4) for i in range(2)]
            s.run()
        assert all(r.status == "done" for r in reqs)
        got = [np.asarray(s.coordinator.engine.value_of(
            r.ref_key(r.graph.outputs["image"]))) for r in reqs]
    for a, b in zip(got, fp32_images):
        assert _rel(a, b) <= IMAGE_TOL["int8"], _rel(a, b)
