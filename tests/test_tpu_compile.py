"""The served path's Pallas kernels compile for a TPU v5e at the served
families' published widths.

Nothing runs: each case lowers the kernel with ``interpret=False`` for a
described (not attached) v5e chip and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  The TPU compiler refuses
here what interpret mode accepts: unaligned slices, too much VMEM, a
program that does not fit the chip.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.diffusion.config import FLUX_DEV_DIT, SD3_DIT, SD35_LARGE_DIT
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.lora_matmul.kernel import lora_matmul_grouped
from repro.kernels.quant_matmul.kernel import quant_matmul

# rows of the sd3 backbone's widest projection (d -> d_ff) at B=1 with
# CFG, rounded to the kernel's 128 tiles
_M = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip cannot read cache entries back: keep the
    # persistent compilation cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# the served attention shapes, each with the tiles the kernel picks from
# them: the compile fails where those tiles do not fit VMEM
@pytest.mark.parametrize("bh,sq,sk,head_dim,dtype", [
    # joint text+image attention: 2 CFG rows x 24 heads, 4096 + 333 tokens
    (2 * SD3_DIT.n_heads, SD3_DIT.tokens, SD3_DIT.tokens, SD3_DIT.head_dim,
     jnp.bfloat16),
    # the stand-in text encoder: 4 heads over text_dim 4096
    (4, SD3_DIT.text_tokens, SD3_DIT.text_tokens, SD3_DIT.text_dim // 4,
     jnp.float32),
    # the backlog's batch of 8 requests, both CFG rows
    (16 * SD3_DIT.n_heads, SD3_DIT.tokens, SD3_DIT.tokens, SD3_DIT.head_dim,
     jnp.bfloat16),
    (2 * SD35_LARGE_DIT.n_heads, SD35_LARGE_DIT.tokens, SD35_LARGE_DIT.tokens,
     SD35_LARGE_DIT.head_dim, jnp.bfloat16),
    (16 * SD35_LARGE_DIT.n_heads, SD35_LARGE_DIT.tokens,
     SD35_LARGE_DIT.tokens, SD35_LARGE_DIT.head_dim, jnp.bfloat16),
    # the sequence-sharded block at k=4: local queries against global K/V
    (2 * SD3_DIT.n_heads, SD3_DIT.text_tokens + SD3_DIT.image_tokens // 4,
     SD3_DIT.tokens, SD3_DIT.head_dim, jnp.bfloat16),
    # FLUX's joint attention: 8 rows (no CFG row) x 24 heads of 128 over
    # 4096 + 512 tokens, 768 x 768 tiles
    (8 * FLUX_DEV_DIT.n_heads, FLUX_DEV_DIT.tokens, FLUX_DEV_DIT.tokens,
     FLUX_DEV_DIT.head_dim, jnp.bfloat16),
], ids=["sd3_joint", "text_encoder", "sd3_joint_b8", "sd35l_joint",
        "sd35l_joint_b8", "sd3_seq_sharded", "flux_joint_b8"])
def test_flash_attention_compiles(one_chip, bh, sq, sk, head_dim, dtype):
    fn = functools.partial(flash_attention, causal=False, interpret=False)
    q = ((bh, sq, head_dim), dtype)
    kv = ((bh, sk, head_dim), dtype)
    assert "tpu_custom_call" in _compiled_hlo(fn, one_chip, q, kv, kv)


@pytest.mark.parametrize("rank_cols", [24, 256], ids=["G3r8", "G32r8"])
def test_grouped_lora_matmul_compiles(one_chip, rank_cols):
    d, d_ff = SD3_DIT.d_model, SD3_DIT.d_ff
    fn = functools.partial(lora_matmul_grouped, interpret=False)
    hlo = _compiled_hlo(fn, one_chip,
                        ((_M, d), jnp.bfloat16), ((d, d_ff), jnp.bfloat16),
                        ((d, rank_cols), jnp.bfloat16),
                        ((rank_cols, d_ff), jnp.bfloat16),
                        ((_M, rank_cols), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_int8_quant_matmul_compiles(one_chip):
    d, d_ff = SD3_DIT.d_model, SD3_DIT.d_ff
    fn = functools.partial(quant_matmul, interpret=False)
    hlo = _compiled_hlo(fn, one_chip,
                        ((_M, d), jnp.int8), ((d, d_ff), jnp.int8),
                        ((_M, 1), jnp.float32), ((1, d_ff), jnp.float32))
    assert "tpu_custom_call" in hlo
