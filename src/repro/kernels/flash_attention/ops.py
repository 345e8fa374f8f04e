"""Jit'd public wrapper around the flash-attention Pallas kernel.

``mha(q, k, v)`` takes the framework-wide ``[B, S, H, D]`` layout, handles
GQA head expansion, and dispatches to the kernel (interpret mode on CPU,
compiled Mosaic on TPU).  The kernel picks its tiles from the shapes
(``kernel.pick_block``).

The kernel carries a ``custom_vjp``: the forward pass is the Pallas
kernel, the backward pass recomputes through the pure-jnp reference
attention (``pallas_call`` has no autodiff rule), so shared call sites —
e.g. ``gqa_attention``'s flash route, which serving and training both
hit — stay differentiable.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(qt, kt, vt, causal, window):
    return flash_attention(qt, kt, vt, causal=causal, window=window,
                           interpret=not _is_tpu())


def _flash_fwd(qt, kt, vt, causal, window):
    return _flash(qt, kt, vt, causal, window), (qt, kt, vt)


def _flash_bwd(causal, window, residuals, g):
    qt, kt, vt = residuals
    _, vjp = jax.vjp(
        lambda q, k, v: attention_ref(q, k, v, causal=causal, window=window),
        qt, kt, vt,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "use_kernel"),
)
def mha(
    q: jax.Array,               # [B, Sq, Hq, D]
    k: jax.Array,               # [B, Sk, Hkv, D]
    v: jax.Array,               # [B, Sk, Hkv, D]
    *,
    causal: bool = False,
    window: Optional[int] = None,
    use_kernel: bool = True,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    if group > 1:               # GQA: expand kv heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hq, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hq, sk, d)
    if use_kernel:
        out = _flash(qt, kt, vt, causal, window)
    else:
        out = attention_ref(qt, kt, vt, causal=causal, window=window)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
