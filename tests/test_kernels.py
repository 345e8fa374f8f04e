"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.telemetry import default_registry
from repro.diffusion.config import FLUX_DEV_DIT, SD3_DIT, SD35_LARGE_DIT
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lora_matmul.ops import lora_apply
from repro.kernels.lora_matmul.ref import lora_matmul_ref
from repro.kernels.rglru_scan.ops import rglru
from repro.kernels.rglru_scan.ref import rglru_ref

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("shape,causal,window,bq,bk", [
    ((2, 128, 128, 64), False, None, 64, 64),
    ((2, 256, 256, 32), True, None, 64, 128),
    ((1, 200, 200, 16), True, 64, 64, 64),      # ragged + sliding window
    ((1, 64, 256, 64), False, None, 32, 64),    # cross-attention shape
    ((2, 100, 300, 8), False, 128, 32, 128),
    ((1, 300, 300, 64), False, None, 128, 128),  # joint-like: ragged last K tile
    ((1, 150, 420, 32), False, None, 64, 128),  # local q slice vs global K/V
    ((1, 1100, 1100, 16), False, None, None, None),  # mha's shape-chosen tiles
    ((2, 256, 256, 128), False, None, 128, 128),  # head_dim 128: VPU row sum
    ((1, 300, 300, 128), False, None, 128, 128),  # ... with a ragged last K tile
    ((1, 200, 330, 96), False, None, 64, 128),  # V widened to 128, 31 zero lanes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(shape, causal, window, bq, bk, dtype):
    bh, sq, sk, d = shape
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (bh, sq, d), dtype=dtype)
    k = jax.random.normal(k2, (bh, sk, d), dtype=dtype)
    v = jax.random.normal(k3, (bh, sk, d), dtype=dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol, rtol=atol)


def _kernels_built():
    return {labels["rowsum"]: value for name, labels, _, value
            in default_registry().collect()
            if name == "flash_attention_kernels_built"}


# the served attention shapes: head_dim 64 carries the softmax row sum in
# the P.V matmul's spare lanes, multiples of 128 sum on the VPU
@pytest.mark.parametrize("bh,s,d,dtype,rowsum", [
    (2 * SD3_DIT.n_heads, SD3_DIT.tokens, SD3_DIT.head_dim, jnp.bfloat16,
     "mxu_lanes"),
    (2 * SD35_LARGE_DIT.n_heads, SD35_LARGE_DIT.tokens,
     SD35_LARGE_DIT.head_dim, jnp.bfloat16, "mxu_lanes"),
    (FLUX_DEV_DIT.n_heads, FLUX_DEV_DIT.tokens, FLUX_DEV_DIT.head_dim,
     jnp.bfloat16, "vpu"),
    # the stand-in text encoder: 4 heads over text_dim 4096
    (4, SD3_DIT.text_tokens, SD3_DIT.text_dim // 4, jnp.float32, "vpu"),
], ids=["sd3_joint", "sd35l_joint", "flux_joint", "text_encoder"])
def test_flash_attention_rowsum_path_by_shape(bh, s, d, dtype, rowsum):
    before = _kernels_built()
    x = jax.ShapeDtypeStruct((bh, s, d), dtype)
    out = jax.eval_shape(flash_attention, x, x, x)
    assert out.shape == (bh, s, d)
    after = _kernels_built()
    assert {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)} == {rowsum: 1.0}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fully_masked_rows_are_zero(dtype):
    """Queries past ``seq_k + window`` see no key: on the spare-lane row
    sum their outputs are 0, not NaN."""
    sq, sk, window = 300, 100, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (1, sq, 64), dtype=dtype)
    k = jax.random.normal(k2, (1, sk, 64), dtype=dtype)
    v = jax.random.normal(k3, (1, sk, 64), dtype=dtype)
    before = _kernels_built().get("mxu_lanes", 0.0)
    out = np.asarray(flash_attention(q, k, v, window=window, block_q=64,
                                     block_k=64), np.float32)
    assert _kernels_built()["mxu_lanes"] == before + 1
    assert np.isfinite(out).all()
    assert not out[:, sk + window:].any()
    ref = np.asarray(attention_ref(q, k, v, window=window), np.float32)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, ref, atol=atol, rtol=atol)


def test_mha_gqa_wrapper():
    from repro.nn.layers import gqa_attention
    q = jax.random.normal(KEY, (2, 64, 8, 32))
    k = jax.random.normal(KEY, (2, 64, 2, 32))
    v = jax.random.normal(KEY, (2, 64, 2, 32))
    out = mha(q, k, v, causal=True)
    ref = gqa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("m,k,n,r", [(128, 128, 128, 8), (200, 96, 160, 16),
                                     (64, 256, 512, 4), (300, 300, 300, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_matches_oracle(m, k, n, r, dtype):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, k), dtype=dtype)
    w = jax.random.normal(ks[1], (k, n), dtype=dtype) / np.sqrt(k)
    a = jax.random.normal(ks[2], (k, r), dtype=dtype) / np.sqrt(k)
    b = jax.random.normal(ks[3], (r, n), dtype=dtype)
    out = lora_apply(x, w, a, b, scale=0.7, block_m=64, block_n=64, block_k=64)
    ref = lora_matmul_ref(x, w, a, b, scale=0.7)
    atol = 1e-4 if dtype == jnp.float32 else 1.5e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol, rtol=atol)


@pytest.mark.parametrize("b,t,d", [(2, 128, 128), (1, 200, 96),
                                   (3, 64, 256), (2, 300, 50)])
def test_rglru_matches_oracle(b, t, d):
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, d)))
    x = jax.random.normal(ks[1], (b, t, d))
    out = rglru(a, x, block_t=64, block_d=64)
    ref = rglru_ref(a, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_rglru_sequential_semantics():
    """Oracle itself vs a literal python recurrence."""
    a = jax.nn.sigmoid(jax.random.normal(KEY, (1, 9, 3)))
    x = jax.random.normal(KEY, (1, 9, 3))
    ref = np.asarray(rglru_ref(a, x))
    h = np.zeros((1, 3))
    an, xn = np.asarray(a), np.asarray(x)
    for t in range(9):
        h = an[:, t] * h + np.sqrt(1 - an[:, t] ** 2) * xn[:, t]
        np.testing.assert_allclose(ref[:, t], h, atol=1e-5)
