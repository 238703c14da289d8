"""Serving substrate: jit-ready prefill / decode step builders and a host
generation loop. These are the ``serve_step`` functions the FaaS layer
registers.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..models import Model
from ..models.knobs import DEFAULT_KNOBS, RunKnobs
from ..sharding.rules import ShardCtx
from .sampler import sample


def make_prefill(model: Model, ctx: ShardCtx = ShardCtx(),
                 knobs: RunKnobs = DEFAULT_KNOBS,
                 cache_len: Optional[int] = None) -> Callable:
    def prefill_step(params, batch):
        return model.prefill(params, batch, ctx, knobs, cache_len=cache_len)
    return prefill_step


def make_decode(model: Model, ctx: ShardCtx = ShardCtx(),
                knobs: RunKnobs = DEFAULT_KNOBS) -> Callable:
    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch, ctx, knobs)
    return decode_step


def generate(
    model: Model,
    params: Any,
    batch: Dict[str, jax.Array],
    n_tokens: int,
    *,
    key: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: int = 0,
    ctx: ShardCtx = ShardCtx(),
    knobs: RunKnobs = DEFAULT_KNOBS,
) -> jax.Array:
    """Host loop: prefill then n_tokens decode steps. Returns (B, n_tokens)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    S = batch["tokens"].shape[1]
    prefill = jax.jit(make_prefill(model, ctx, knobs, cache_len=S + n_tokens))
    decode = jax.jit(make_decode(model, ctx, knobs))
    logits, cache = prefill(params, batch)
    toks = []
    tok = sample(logits, key, temperature, top_k)
    toks.append(tok)
    for i in range(n_tokens - 1):
        key, sub = jax.random.split(key)
        logits, cache = decode(params, cache, {"tokens": tok[:, None]})
        tok = sample(logits, sub, temperature, top_k)
        toks.append(tok)
    return jnp.stack(toks, axis=1)
