"""Jitted public wrappers around the Pallas kernels.

These keep the *model* layout at the boundary (B, S, H, D) and handle layout
transposition and head-dim padding to MXU-friendly multiples. Every call
compiles to Mosaic for the TPU unless the caller passes ``interpret=True``
(which executes the kernel body on any backend, for correctness tests); a
compiled call on another backend fails in the Pallas lowering.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_kernel
from .flash_attention import flash_attention_kernel
from .rglru_scan import rglru_scan_kernel
from .ssd_scan import ssd_scan_kernel


def _pad_last(x: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    d = x.shape[-1]
    pad = (-d) % multiple
    if pad:
        cfgs = [(0, 0)] * x.ndim
        cfgs[-1] = (0, pad)
        x = jnp.pad(x, cfgs)
    return x, pad


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "softmax_scale",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,                 # (B, Sq, H, D) — model layout
    k: jax.Array,                 # (B, Sk, KVH, D)
    v: jax.Array,                 # (B, Sk, KVH, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    D = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    # pad head dim to an MXU-friendly multiple (zeros do not perturb scores)
    q, _ = _pad_last(q, 128)
    k, _ = _pad_last(k, 128)
    v, pad_v = _pad_last(v, 128)
    qt = jnp.moveaxis(q, 2, 1)     # (B, H, Sq, Dp)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    out = flash_attention_kernel(
        qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
        softmax_scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    out = jnp.moveaxis(out, 1, 2)  # (B, Sq, H, Dp)
    if pad_v:
        out = out[..., :v.shape[-1] - pad_v]
    return out


@functools.partial(
    jax.jit,
    static_argnames=("window", "softmax_scale", "block_s", "interpret"))
def decode_attention(
    q: jax.Array,                 # (B, 1, H, D) — model layout
    k_cache: jax.Array,           # (B, S, KVH, D)
    v_cache: jax.Array,           # (B, S, KVH, D)
    lengths: jax.Array,           # (B,)
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    D = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    q2, _ = _pad_last(q, 128)
    k2, _ = _pad_last(k_cache, 128)
    v2, pad_v = _pad_last(v_cache, 128)
    out = decode_attention_kernel(
        q2[:, 0],                                  # (B, H, Dp)
        jnp.moveaxis(k2, 2, 1),                    # (B, KVH, S, Dp)
        jnp.moveaxis(v2, 2, 1),
        lengths.astype(jnp.int32),
        window=window, softmax_scale=scale, block_s=block_s,
        interpret=interpret)
    out = out[:, None]                             # (B, 1, H, Dp)
    if pad_v:
        out = out[..., :v_cache.shape[-1]]
    return out


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_w", "interpret"))
def rglru(
    a: jax.Array,                 # (B, S, W) decays
    b: jax.Array,                 # (B, S, W)
    *,
    block_s: int = 256,
    block_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    return rglru_scan_kernel(a, b, block_s=block_s, block_w=block_w,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jax.Array,                 # (B, S, H, P) — model layout, dt-scaled
    a: jax.Array,                 # (B, S, H)
    Bm: jax.Array,                # (B, S, H, N)
    Cm: jax.Array,                # (B, S, H, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    xt = jnp.moveaxis(x, 2, 1)     # (B, H, S, P)
    at = jnp.moveaxis(a, 2, 1)     # (B, H, S)
    Bt = jnp.moveaxis(Bm, 2, 1)
    Ct = jnp.moveaxis(Cm, 2, 1)
    y, state = ssd_scan_kernel(xt, at, Bt, Ct, chunk=chunk,
                               interpret=interpret)
    return jnp.moveaxis(y, 1, 2), state
