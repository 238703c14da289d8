"""Encoder–decoder backbone (seamless-m4t-large-v2).

The audio frontend is a STUB per the assignment: ``input_specs()`` provides
precomputed frame embeddings (B, S_src, d_model); a learned adaptor
projection stands in for the real feature pipeline. The text decoder is a
standard causal stack with cross-attention; decode caches both the decoder
self-attention KV and the (computed-once) cross-attention KV.

The assigned ``seq_len`` is interpreted as the *total* token budget:
S_src = S_tgt = seq_len // 2 (recorded in DESIGN.md).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx
from . import attention as attn
from .common import (
    chunked_attention,
    cross_entropy,
    decode_attention,
    embed_tokens,
    lm_logits,
    rms_norm,
    swiglu,
)
from .knobs import DEFAULT_KNOBS
from .params import ParamSpec, stack
from .transformer import _remat, ffn_spec


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _cross_spec(cfg: ModelConfig) -> dict:
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    return {
        "wq": ParamSpec((d, H * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wk": ParamSpec((d, KVH * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wv": ParamSpec((d, KVH * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wo": ParamSpec((H * hd, d), ("heads_dim", "embed"), "scaled_normal"),
    }


def enc_block_spec(cfg: ModelConfig) -> dict:
    return {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "attn": attn.attn_spec(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "ffn": ffn_spec(cfg),
    }


def dec_block_spec(cfg: ModelConfig) -> dict:
    return {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "attn": attn.attn_spec(cfg),
        "ln_x": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "cross": _cross_spec(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "ffn": ffn_spec(cfg),
    }


def model_spec(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab()
    return {
        "embed": {"tok": ParamSpec((v, cfg.d_model), ("vocab", "embed"),
                                   "normal", 0.02)},
        "frame_proj": ParamSpec((cfg.d_model, cfg.d_model),
                                ("embed", "act_embed"), "scaled_normal"),
        "enc_blocks": stack(enc_block_spec(cfg), cfg.encdec.n_encoder_layers),
        "enc_ln_f": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "dec_blocks": stack(dec_block_spec(cfg), cfg.n_layers),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "lm_head": ParamSpec((cfg.d_model, v), ("embed", "vocab"),
                             "scaled_normal"),
    }


# ---------------------------------------------------------------------------
# Cross attention
# ---------------------------------------------------------------------------

def _cross_kv(cfg, p, mem):
    B, Ss, _ = mem.shape
    KVH, hd = cfg.n_kv_heads, cfg.head_dim_
    k = jnp.einsum("bsd,dk->bsk", mem, p["wk"]).reshape(B, Ss, KVH, hd)
    v = jnp.einsum("bsd,dk->bsk", mem, p["wv"]).reshape(B, Ss, KVH, hd)
    return k, v


def _cross_full(cfg, p, h, mem, ctx, knobs, collect=False):
    B, S, _ = h.shape
    H, hd = cfg.n_heads, cfg.head_dim_
    q = jnp.einsum("bsd,dk->bsk", h, p["wq"]).reshape(B, S, H, hd)
    k, v = _cross_kv(cfg, p, mem)
    out = chunked_attention(q, k, v, causal=False,
                            q_block=knobs.q_block, kv_block=knobs.kv_block)
    y = jnp.einsum("bsk,kd->bsd", out.reshape(B, S, -1), p["wo"])
    if collect:
        return y, (k, v)
    return y


def _cross_decode(cfg, p, h, xk, xv):
    B = h.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim_
    q = jnp.einsum("bsd,dk->bsk", h, p["wq"]).reshape(B, 1, H, hd)
    lengths = jnp.full((B,), xk.shape[1], jnp.int32)
    out = decode_attention(q, xk, xv, lengths)
    return jnp.einsum("bsk,kd->bsd", out.reshape(B, 1, -1), p["wo"])


# ---------------------------------------------------------------------------
# Encoder / decoder stacks
# ---------------------------------------------------------------------------

def encode(cfg, params, frames, ctx, knobs):
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.einsum("bsd,de->bse", frames.astype(dtype), params["frame_proj"])
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, lp):
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        x = x + attn.attn_full(cfg, lp["attn"], h, positions, ctx, knobs,
                               causal=False)
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                       lp["ffn"]["w_down"])
        return x, None

    x, _ = jax.lax.scan(_remat(body, knobs.remat), x, params["enc_blocks"])
    return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


def decode_stack(cfg, params, x, mem, positions, ctx, knobs,
                 collect: bool = False):
    def body(x, lp):
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if collect:
            a, kv = attn.attn_full(cfg, lp["attn"], h, positions, ctx, knobs,
                                   return_kv=True)
        else:
            a = attn.attn_full(cfg, lp["attn"], h, positions, ctx, knobs)
            kv = None
        x = x + a
        h = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        if collect:
            c, xkv = _cross_full(cfg, lp["cross"], h, mem, ctx, knobs,
                                 collect=True)
        else:
            c = _cross_full(cfg, lp["cross"], h, mem, ctx, knobs)
            xkv = None
        x = x + c
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                       lp["ffn"]["w_down"])
        return x, (kv, xkv)

    body_fn = body if collect else _remat(body, knobs.remat)
    x, states = jax.lax.scan(body_fn, x, params["dec_blocks"])
    return rms_norm(x, params["ln_f"], cfg.norm_eps), states


# ---------------------------------------------------------------------------
# Model API
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            z_loss: float = 0.0):
    dtype = jnp.dtype(cfg.dtype)
    mem = encode(cfg, params, batch["frames"], ctx, knobs)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    B, St = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32)[None], (B, St))
    x, _ = decode_stack(cfg, params, x, mem, positions, ctx, knobs)
    logits = lm_logits(x, params["lm_head"], cfg.vocab_size)
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"), z_loss)
    return ce, {"ce": ce, "moe_aux": jnp.float32(0.0)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype,
               src_len: Optional[int] = None) -> dict:
    KVH, hd, L = cfg.n_kv_heads, cfg.head_dim_, cfg.n_layers
    Ss = src_len if src_len is not None else max_seq
    return {
        "layers": {
            "k": jnp.zeros((L, batch, max_seq, KVH, hd), dtype),
            "v": jnp.zeros((L, batch, max_seq, KVH, hd), dtype),
            "xk": jnp.zeros((L, batch, Ss, KVH, hd), dtype),
            "xv": jnp.zeros((L, batch, Ss, KVH, hd), dtype),
        },
        "pos": jnp.zeros((), jnp.int32),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig) -> dict:
    kv = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
    return {"layers": {"k": kv, "v": kv, "xk": kv, "xv": kv},
            "pos": (), "lengths": ("cache_batch",)}


def prefill(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            cache_len=None):
    """Encode frames + teacher-forced decoder prefix; build both caches."""
    dtype = jnp.dtype(cfg.dtype)
    mem = encode(cfg, params, batch["frames"], ctx, knobs)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    B, St = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32)[None], (B, St))
    x, (kv, xkv) = decode_stack(cfg, params, x, mem, positions, ctx, knobs,
                                collect=True)
    logits = lm_logits(x[:, -1:], params["lm_head"], cfg.vocab_size)
    max_seq = cache_len or St

    def pad(t):
        cfgs = [(0, 0)] * t.ndim
        cfgs[2] = (0, max_seq - t.shape[2])
        return jnp.pad(t, cfgs)

    cache = {
        "layers": {"k": pad(kv[0]), "v": pad(kv[1]),
                   "xk": xkv[0], "xv": xkv[1]},
        "pos": jnp.int32(St),
        "lengths": jnp.full((B,), St, jnp.int32),
    }
    return logits[:, 0], cache


def decode_step(cfg, params, cache, batch, ctx=ShardCtx(),
                knobs=DEFAULT_KNOBS):
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    pos, lengths = cache["pos"], cache["lengths"] + 1

    def body(x, xs):
        lp, cache_l = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, new_self = attn.attn_decode(
            cfg, lp["attn"], h, {"k": cache_l["k"], "v": cache_l["v"]},
            pos, lengths, ctx)
        x = x + a
        h = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        x = x + _cross_decode(cfg, lp["cross"], h, cache_l["xk"],
                              cache_l["xv"])
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                       lp["ffn"]["w_down"])
        new_cache_l = {"k": new_self["k"], "v": new_self["v"],
                       "xk": cache_l["xk"], "xv": cache_l["xv"]}
        return x, new_cache_l

    x, new_layers = jax.lax.scan(body, x,
                                 (params["dec_blocks"], cache["layers"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x, params["lm_head"], cfg.vocab_size)
    return logits[:, 0], {"layers": new_layers, "pos": pos + 1,
                          "lengths": lengths}
