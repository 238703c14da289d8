"""The two products a reference computes its projections with.

``full`` is float32 at ``highest`` precision: on a TPU a float32 product
runs in bfloat16 passes unless told otherwise. ``fp8`` is the control: both
operands rounded to float8 (e4m3) with a scale per row of the activations
and per output column of the weights, then multiplied exactly, which is
what serving in float8 would do to the configuration's bfloat16.
"""
from __future__ import annotations


def full(x, w):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _to_fp8(a, axis):
    import jax.numpy as jnp

    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / float(jnp.finfo(f8).max)
    return (a / scale).astype(f8).astype(jnp.float32) * scale


def fp8(x, w):
    return full(_to_fp8(x, -1), _to_fp8(w, -2))


PRODUCTS = {"full": full, "fp8": fp8}
