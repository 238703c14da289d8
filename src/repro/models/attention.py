"""Attention sub-blocks: standard GQA (optionally local/windowed) and MLA
(multi-head latent attention, MiniCPM3/DeepSeek-V2 style), each with a
full-sequence path (train/prefill) and a KV-cache decode path.

The decode path for MLA uses the *absorbed* formulation: scores and context
are computed directly against the latent cache (c_kv, k_pe) so the per-head
K/V are never reconstructed for the whole cache — this is the TPU-friendly
memory form (cache is rank·S instead of 2·H·hd·S).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx
from .knobs import RunKnobs
from .common import (
    NEG_INF,
    apply_mrope,
    apply_rope,
    chunked_attention,
    decode_attention,
    rms_norm,
)
from .params import ParamSpec


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig) -> dict:
    if cfg.mla is not None:
        return _mla_spec(cfg)
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    spec = {
        "wq": ParamSpec((d, H * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wk": ParamSpec((d, KVH * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wv": ParamSpec((d, KVH * hd), ("embed", "heads_dim"), "scaled_normal"),
        "wo": ParamSpec((H * hd, d), ("heads_dim", "embed"), "scaled_normal"),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((H * hd,), ("heads_dim",), "zeros")
        spec["bk"] = ParamSpec((KVH * hd,), ("heads_dim",), "zeros")
        spec["bv"] = ParamSpec((KVH * hd,), ("heads_dim",), "zeros")
    return spec


def _mla_spec(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": ParamSpec((d, H * qk), ("embed", "heads_dim"), "scaled_normal")}
    else:
        q = {
            "w_dq": ParamSpec((d, m.q_lora_rank), ("embed", "mla_rank"), "scaled_normal"),
            "q_norm": ParamSpec((m.q_lora_rank,), ("mla_rank",), "zeros"),
            "w_uq": ParamSpec((m.q_lora_rank, H * qk), ("mla_rank", "heads_dim"),
                              "scaled_normal"),
        }
    return {
        **q,
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", "mla_rank"), "scaled_normal"),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("mla_rank",), "zeros"),
        "w_uk": ParamSpec((m.kv_lora_rank, H * m.qk_nope_head_dim),
                          ("mla_rank", "heads_dim"), "scaled_normal"),
        "w_uv": ParamSpec((m.kv_lora_rank, H * m.v_head_dim),
                          ("mla_rank", "heads_dim"), "scaled_normal"),
        "wo": ParamSpec((H * m.v_head_dim, d), ("heads_dim", "embed"), "scaled_normal"),
    }


# ---------------------------------------------------------------------------
# Standard GQA
# ---------------------------------------------------------------------------

def _qkv(cfg: ModelConfig, p: dict, h: jax.Array):
    B, S, _ = h.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = jnp.einsum("bsd,dk->bsk", h, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", h, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, H, hd), k.reshape(B, S, KVH, hd),
            v.reshape(B, S, KVH, hd))


def _rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if cfg.vlm is not None:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.vlm.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def attn_full(
    cfg: ModelConfig,
    p: dict,
    h: jax.Array,                 # (B, S, d) — already normed
    positions: jax.Array,         # (B, S) or (3, B, S) for M-RoPE
    ctx: ShardCtx,
    knobs: RunKnobs,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    return_kv: bool = False,
):
    if cfg.mla is not None:
        return mla_full(cfg, p, h, positions, ctx, knobs,
                        return_kv=return_kv)
    q, k, v = _qkv(cfg, p, h)
    q, k = _rope(cfg, q, positions), _rope(cfg, k, positions)
    q = ctx.constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = ctx.constrain(k, ("act_batch", "act_seq", "act_heads", None))
    v = ctx.constrain(v, ("act_batch", "act_seq", "act_heads", None))
    w = window if window is not None else (
        cfg.recurrent.attention_window
        if (cfg.attention_kind == "local" and cfg.recurrent) else None)
    out = chunked_attention(q, k, v, causal=causal, window=w,
                            q_block=knobs.q_block, kv_block=knobs.kv_block)
    B, S = h.shape[:2]
    y = jnp.einsum("bsk,kd->bsd", out.reshape(B, S, -1), p["wo"])
    if return_kv:
        return y, (k, v)
    return y


def attn_cache_init(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    KVH, hd = cfg.n_kv_heads, cfg.head_dim_
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_seq, KVH, hd), dtype),
        "v": jnp.zeros((batch, max_seq, KVH, hd), dtype),
    }


def attn_cache_axes(cfg: ModelConfig) -> dict:
    if cfg.mla is not None:
        return {
            "c_kv": ("cache_batch", "cache_seq", None),
            "k_pe": ("cache_batch", "cache_seq", None),
        }
    return {
        "k": ("cache_batch", "cache_seq", "cache_heads", None),
        "v": ("cache_batch", "cache_seq", "cache_heads", None),
    }


def attn_cache_from_prefill(cfg: ModelConfig, kv, max_seq: int) -> dict:
    """Pad prefill-computed K/V (or MLA latents) out to the cache buffer."""
    def pad(x):
        pad_len = max_seq - x.shape[1]
        cfgs = [(0, 0)] * x.ndim
        cfgs[1] = (0, pad_len)
        return jnp.pad(x, cfgs)
    if cfg.mla is not None:
        c_kv, k_pe = kv
        return {"c_kv": pad(c_kv), "k_pe": pad(k_pe)}
    k, v = kv
    return {"k": pad(k), "v": pad(v)}


def attn_decode(
    cfg: ModelConfig,
    p: dict,
    h: jax.Array,                 # (B, 1, d) — already normed
    cache: dict,                  # per-layer cache
    pos: jax.Array,               # () int32 — write index
    lengths: jax.Array,           # (B,) valid lengths incl. this token
    ctx: ShardCtx,
    *,
    window: Optional[int] = None,
) -> Tuple[jax.Array, dict]:
    if cfg.mla is not None:
        return mla_decode(cfg, p, h, cache, pos, lengths, ctx)
    B = h.shape[0]
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    if cfg.vlm is not None:
        positions = jnp.broadcast_to(pos, (3, B, 1)).astype(jnp.int32)
    q, k, v = _qkv(cfg, p, h)
    q, k = _rope(cfg, q, positions), _rope(cfg, k, positions)
    k_cache = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                       (0, pos, 0, 0))
    v_cache = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                       (0, pos, 0, 0))
    k_cache = ctx.constrain(k_cache, attn_cache_axes(cfg)["k"])
    v_cache = ctx.constrain(v_cache, attn_cache_axes(cfg)["v"])
    out = decode_attention(q, k_cache, v_cache, lengths, window=window)
    y = jnp.einsum("bsk,kd->bsd", out.reshape(B, 1, -1), p["wo"])
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def mla_rope_freqs(cfg: ModelConfig) -> np.ndarray:
    """(rope_dim/2,) inverse frequencies of the MLA rope dims. With YaRN,
    the interpolated (``/ factor``) and extrapolated bands are blended by
    a linear ramp between the correction dims of ``beta_fast`` (floor)
    and ``beta_slow`` (ceil)."""
    m = cfg.mla
    dim, base = m.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    y = m.yarn
    if y is None:
        return extra
    inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                       / dim))
    orig = y.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(y.beta_fast, dim, base, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(y.beta_slow, dim, base, orig)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim)`` squared."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if m.yarn is not None and m.yarn.mscale_all_dim:
        scale *= yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """Rotate the rope dims ``x`` (B, S, H, rope_dim). Interleaved pairs
    are first laid out as halves, the layout the rotation (and so the
    cache) holds them in."""
    m = cfg.mla
    if m.rope_interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if m.yarn is None:
        return apply_rope(x, positions, cfg.rope_theta)
    y = m.yarn
    return apply_rope(x, positions, cfg.rope_theta,
                      inv_freq=jnp.asarray(mla_rope_freqs(cfg)),
                      mscale=yarn_mscale(y.factor, y.mscale)
                      / yarn_mscale(y.factor, y.mscale_all_dim))


def _mla_q(cfg: ModelConfig, p: dict, h: jax.Array, positions: jax.Array):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = h.shape
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dk->bsk", h, p["wq"])
    else:
        dq = rms_norm(jnp.einsum("bsd,dr->bsr", h, p["w_dq"]), p["q_norm"],
                      cfg.norm_eps)
        q = jnp.einsum("bsr,rk->bsk", dq, p["w_uq"])
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_pe = _mla_rope(cfg, q[..., m.qk_nope_head_dim:], positions)
    return q_nope, q_pe


def _mla_latents(cfg: ModelConfig, p: dict, h: jax.Array, positions: jax.Array):
    m = cfg.mla
    dkv = jnp.einsum("bsd,dr->bsr", h, p["w_dkv"])
    c_kv = rms_norm(dkv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(cfg, dkv[..., None, m.kv_lora_rank:],
                     positions)[:, :, 0]                  # (B, S, rope_dim)
    return c_kv, k_pe


@jax.named_scope("mla")
def mla_full(cfg: ModelConfig, p: dict, h: jax.Array, positions: jax.Array,
             ctx: ShardCtx, knobs: RunKnobs, *, return_kv: bool = False):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = h.shape
    q_nope, q_pe = _mla_q(cfg, p, h, positions)
    c_kv, k_pe = _mla_latents(cfg, p, h, positions)
    # reconstruct per-head K/V for the full-sequence path
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_uk)
    v = jnp.einsum("bsr,rhv->bshv", c_kv, w_uv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    q = ctx.constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = ctx.constrain(k, ("act_batch", "act_seq", "act_heads", None))
    v = ctx.constrain(v, ("act_batch", "act_seq", "act_heads", None))
    out = chunked_attention(q, k, v.astype(q.dtype), causal=True,
                            q_block=knobs.q_block, kv_block=knobs.kv_block,
                            softmax_scale=mla_softmax_scale(cfg))
    y = jnp.einsum("bsk,kd->bsd", out.reshape(B, S, -1), p["wo"])
    if return_kv:
        return y, (c_kv, k_pe)
    return y


@jax.named_scope("mla")
def mla_decode(cfg: ModelConfig, p: dict, h: jax.Array, cache: dict,
               pos: jax.Array, lengths: jax.Array, ctx: ShardCtx):
    m, H = cfg.mla, cfg.n_heads
    B = h.shape[0]
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    q_nope, q_pe = _mla_q(cfg, p, h, positions)          # (B,1,H,·)
    c_new, kpe_new = _mla_latents(cfg, p, h, positions)  # (B,1,r), (B,1,rope)
    c_kv = lax.dynamic_update_slice(cache["c_kv"],
                                    c_new.astype(cache["c_kv"].dtype),
                                    (0, pos, 0))
    k_pe = lax.dynamic_update_slice(cache["k_pe"],
                                    kpe_new.astype(cache["k_pe"].dtype),
                                    (0, pos, 0))
    c_kv = ctx.constrain(c_kv, ("cache_batch", "cache_seq", None))
    k_pe = ctx.constrain(k_pe, ("cache_batch", "cache_seq", None))

    # absorbed scores: q_nope^T (W_uk c) == (W_uk^T q_nope)^T c
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)   # (B,1,H,r)
    s = (jnp.einsum("bqhr,bsr->bhqs", q_abs, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhe,bse->bhqs", q_pe, k_pe,
                      preferred_element_type=jnp.float32))
    s = s * mla_softmax_scale(cfg)
    S = c_kv.shape[1]
    mask = jnp.arange(S)[None] < lengths[:, None]        # (B, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    attn = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", attn, c_kv,
                         preferred_element_type=jnp.float32)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat.astype(h.dtype), w_uv)
    y = jnp.einsum("bsk,kd->bsd", out.reshape(B, 1, -1), p["wo"])
    return y, {"c_kv": c_kv, "k_pe": k_pe}
