"""Weights from a seed, made in one jitted call on the device.

The served weights follow one rule, which the reference restates here:
each parameter at path ``p`` (``jax.tree_util.keystr`` of its place in the
nested dict, e.g. ``['blocks']['attn']['wq']``) draws from
``fold_in(PRNGKey(seed), crc32(p) % (2**31 - 1))``; ``normal`` leaves are
``N(0, 1) * scale``, ``scaled_normal`` leaves ``N(0, 1) / sqrt(fan_in)``
with ``fan_in`` the second-to-last dimension, ``zeros``/``ones`` are
constants. Stacked layers carry a leading layer axis.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

Spec = Tuple[str, Tuple[int, ...], str, float]   # path, shape, init, scale

SEED_MODULUS = 2 ** 31                            # run seed → weight seed


def weight_seed(seed: int) -> int:
    """The weight seed for a run seed; run seeds exceed 32 bits."""
    return int(seed) % SEED_MODULUS


def _leaf(spec: Spec, key):
    import jax
    import jax.numpy as jnp

    path, shape, init, scale = spec
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31 - 1))
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "normal":
        return jax.random.normal(k, shape, jnp.float32) * scale
    if init == "scaled_normal":
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return jax.random.normal(k, shape, jnp.float32) * std
    raise ValueError(f"unknown init {init!r}")


def make(specs: List[Spec], seed: int) -> Dict[str, object]:
    """``{path: float32 array}`` for every spec, in one jitted call."""
    import jax

    def build(key):
        return {s[0]: _leaf(s, key) for s in specs}

    return jax.jit(build)(jax.random.PRNGKey(weight_seed(seed)))
