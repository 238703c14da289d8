"""Unified model API.

``get_model(cfg)`` returns a :class:`Model` facade dispatching to the family
implementation (transformer / ssm / rglru / encdec). The facade is what the
FaaS layer registers as *functions* (train_step / prefill / decode_step) and
what the dry-run lowers.

``input_specs(cfg, shape, kind)`` builds ShapeDtypeStruct stand-ins for every
model input — weak-type-correct, shardable, no device allocation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ModelConfig, ShapeConfig
from ..sharding.rules import ShardCtx
from . import encdec, params as P, rglru, ssm, transformer
from .knobs import DEFAULT_KNOBS, RunKnobs


def _family_module(cfg: ModelConfig):
    if cfg.family == "ssm":
        return ssm
    if cfg.family == "hybrid":
        return rglru
    if cfg.family == "audio":
        return encdec
    return transformer       # dense | moe | vlm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def mod(self):
        return _family_module(self.cfg)

    # ---- parameters --------------------------------------------------------
    def spec(self) -> dict:
        return self.mod.model_spec(self.cfg)

    def init(self, key: jax.Array, dtype=None) -> Any:
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return P.init_params(self.spec(), key, dtype)

    def abstract_params(self, dtype=None) -> Any:
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return P.abstract_params(self.spec(), dtype)

    def param_axes(self) -> Any:
        return P.logical_axes(self.spec())

    def param_count(self) -> int:
        return P.count_params(self.spec())

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE discount). Used for 6·N·D."""
        total = self.param_count()
        m = self.cfg.moe
        if m is None:
            return total
        inactive_per_layer = 3 * (m.n_held - min(m.top_k, m.n_held)) * \
            self.cfg.d_model * m.d_ff_expert
        return total - inactive_per_layer * (self.cfg.n_layers
                                             - m.first_k_dense)

    # ---- computations ------------------------------------------------------
    def compute_params(self, params):
        """The weights as every computation reads them: float leaves in the
        activation dtype (mixed precision). Training keeps ``param_dtype``
        masters and each step casts them on entry; a serving environment
        stores this tree, so the cast in its steps leaves no op."""
        return P.cast_floats(params, jnp.dtype(self.cfg.dtype))

    def loss(self, params, batch, ctx: ShardCtx = ShardCtx(),
             knobs: RunKnobs = DEFAULT_KNOBS, z_loss: float = 0.0):
        return self.mod.loss_fn(self.cfg, self.compute_params(params), batch,
                                ctx, knobs, z_loss)

    def prefill(self, params, batch, ctx: ShardCtx = ShardCtx(),
                knobs: RunKnobs = DEFAULT_KNOBS, cache_len=None):
        return self.mod.prefill(self.cfg, self.compute_params(params), batch,
                                ctx, knobs, cache_len=cache_len)

    def decode_step(self, params, cache, batch, ctx: ShardCtx = ShardCtx(),
                    knobs: RunKnobs = DEFAULT_KNOBS):
        return self.mod.decode_step(self.cfg, self.compute_params(params),
                                    cache, batch, ctx, knobs)

    # ---- caches ------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None, **kw):
        dtype = dtype or jnp.dtype(self.cfg.dtype)
        return self.mod.init_cache(self.cfg, batch, max_seq, dtype, **kw)

    def abstract_cache(self, batch: int, max_seq: int, dtype=None, **kw):
        dtype = dtype or jnp.dtype(self.cfg.dtype)
        return jax.eval_shape(
            lambda: self.mod.init_cache(self.cfg, batch, max_seq, dtype, **kw))

    def cache_axes(self) -> dict:
        return self.mod.cache_axes(self.cfg)


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins for the dry-run & FaaS signatures)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                kind: Optional[str] = None) -> Dict[str, Any]:
    """Abstract inputs for one (arch × shape) cell.

    kind: "train" | "prefill" | "decode" (defaults to shape.kind).
    For decode, the cache spec is produced separately via
    :meth:`Model.abstract_cache` — this returns only the step inputs.
    """
    kind = kind or shape.kind
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    tok = lambda b, s: jax.ShapeDtypeStruct((b, s), i32)

    if kind == "decode":
        return {"tokens": tok(B, 1)}

    if cfg.family == "audio":
        half = S // 2
        specs = {"frames": jax.ShapeDtypeStruct((B, half, cfg.d_model), bf16),
                 "tokens": tok(B, half)}
        if kind == "train":
            specs["labels"] = tok(B, half)
        return specs

    if cfg.family == "vlm":
        pfx = cfg.vlm.vision_prefix_len
        text = S - pfx
        specs = {"tokens": tok(B, text),
                 "patches": jax.ShapeDtypeStruct((B, pfx, cfg.d_model), bf16)}
        if kind == "train":
            specs["labels"] = tok(B, text)
        return specs

    specs = {"tokens": tok(B, S)}
    if kind == "train":
        specs["labels"] = tok(B, S)
    return specs


def concrete_batch(cfg: ModelConfig, shape: ShapeConfig, key: jax.Array,
                   kind: Optional[str] = None) -> Dict[str, jax.Array]:
    """Random concrete inputs matching input_specs (for smoke tests/examples)."""
    specs = input_specs(cfg, shape, kind)
    out = {}
    for name, s in specs.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, s.shape, 0, cfg.vocab_size,
                                           s.dtype)
        else:
            out[name] = jax.random.normal(sub, s.shape, jnp.float32).astype(s.dtype)
    return out


def decode_cache_kwargs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Per-family kwargs for init_cache at a decode cell."""
    if cfg.family == "audio":
        half = shape.seq_len // 2
        return {"batch": shape.global_batch, "max_seq": half,
                "src_len": half}
    return {"batch": shape.global_batch, "max_seq": shape.seq_len}
