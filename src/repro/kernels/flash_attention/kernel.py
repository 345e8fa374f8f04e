"""Blockwise online-softmax attention (FlashAttention) as a Pallas TPU kernel.

TPU-native design notes (HARDWARE ADAPTATION):

* Tiling is chosen for the VMEM hierarchy: a ``(block_q, head_dim)`` query
  tile stays VMEM-resident across the whole K/V sweep; K/V stream through
  in ``(block_k, head_dim)`` tiles.
* Tile sizes come from the input's shapes (``pick_block``).  Each grid
  step pays a fixed cost (pipeline bookkeeping, the K/V DMAs, the m/l
  scratch round trips) and each query tile re-reads all of K and V, so
  tiles are as large as VMEM allows.  Per sequence dimension the cap is
  the largest multiple of 128 up to ``MAX_BLOCK`` whose q, k or v tile
  holds at most ``TILE_BYTES``; a sequence within the cap is one tile, a
  longer one takes the multiple of 128 between half the cap and the cap
  that pads it least, the larger on a tie.  sd3's joint sequence (4429
  tokens at head_dim 64) takes 896 x 896 tiles, 5 x 5 steps, padded to
  4480; the stand-in text encoder (333 tokens at head_dim 1024, f32)
  keeps 128-row tiles.  On a TPU v5e, one call at 384 heads of the joint
  sequence in bf16 took 126 ms with 256 x 256 tiles, 63 ms at 512,
  45.7 ms at 640, 40.2 ms at 896 and 41.5 ms at 1024 (which pads to
  5120); with 128 x 128 tiles and a mask in every tile it took 264 ms.
* The k-sweep is the **last grid dimension**, which Mosaic executes
  sequentially per (bh, q) tile — the running max/sum/accumulator live in
  VMEM scratch across those iterations (the TPU analogue of a CUDA
  thread-block's shared-memory accumulators).
* A mask costs two iotas, a compare and two selects over every score, so
  only a tile where a mask can bite builds one: the ragged last K tile,
  whose rows past ``seq_k`` are padding that Pallas leaves unspecified,
  and every tile of a causal or sliding-window call.  Tiles wholly inside
  ``seq_k`` of a full-attention call take a body with no mask; a
  ``pl.when`` on the K tile index picks the body.  Causal and
  sliding-window masks are applied with absolute-position iota against
  the tile offsets, so the same kernel serves full, causal, and SWA
  attention (the long_500k decode variant).
* QK^T contracts the last dimension of both tiles (an NT ``dot_general``),
  so K is never transposed.  Both matmuls take f32 operands: at the
  default precision Mosaic feeds the MXU one bf16 pass, and under
  ``jax.default_matmul_precision("highest")`` it honours f32 (it refuses
  bf16 operands there).  m, l, the accumulator and exp are f32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 1024
# VMEM bytes of one q, k or v tile at most: 1024 rows of an f32 head of
# 64.  Wider rows take proportionally fewer.
TILE_BYTES = 256 * 1024
# contract the last dimension of both operands: [bq, d] x [bk, d] -> [bq, bk]
_NT = (((1,), (1,)), ((), ()))


def pick_block(seq: int, head_dim: int, itemsize: int) -> int:
    """The tile along a sequence of ``seq`` positions of ``head_dim``
    elements of ``itemsize`` bytes, by the rule in the module docstring."""
    rows = TILE_BYTES // (head_dim * itemsize) // LANES * LANES
    cap = min(MAX_BLOCK, max(LANES, rows))
    if seq <= cap:
        return seq
    return min(range(max(LANES, cap // 2), cap + 1, LANES),
               key=lambda b: (pl.cdiv(seq, b) * b, -b))


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def sweep(masked: bool):
        q = q_ref[...].astype(jnp.float32) * scale      # [bq, d]
        k = k_ref[...].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(q, k, _NT)              # [bq, bk]
        v = v_ref[...].astype(jnp.float32)              # [bk, d]
        if masked:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < seq_k                        # padding guard
            if causal or window is not None:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window is not None:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
            # sanitize padded value rows (OOB tile reads are unspecified)
            valid_v = (ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)) < seq_k
            v = jnp.where(valid_v, v, 0.0)

        m_prev = m_scratch[...]                         # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # [bq, bk]
        if masked:
            # zero masked probs explicitly: a fully-masked tile must
            # contribute 0, not exp(NEG_INF - NEG_INF) = 1
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(p, axis=-1,
                                                          keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + p @ v
        m_scratch[...] = m_new

    if causal or window is not None:
        sweep(masked=True)
    elif seq_k % block_k:
        pl.when(ki < nk - 1)(functools.partial(sweep, masked=False))
        pl.when(ki == nk - 1)(functools.partial(sweep, masked=True))
    else:
        sweep(masked=False)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scratch[...]
        l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
        o_ref[...] = (acc_scratch[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,               # [BH, Sq, D]
    k: jax.Array,               # [BH, Sk, D]
    v: jax.Array,               # [BH, Sk, D]
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = True,
) -> jax.Array:
    """Attention of ``q`` over ``k``/``v``; ``block_q``/``block_k`` of
    ``None`` take ``pick_block`` of the sequence lengths, and an explicit
    block is clamped to its sequence."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    size = jnp.dtype(q.dtype).itemsize
    block_q = min(block_q or pick_block(sq, d, size), sq)
    block_k = min(block_k or pick_block(sk, d, size), sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_k=sk,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
