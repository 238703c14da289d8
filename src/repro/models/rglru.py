"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local
(sliding-window) attention blocks in a 2:1 pattern.

Layer layout for L layers: ``head = L % 3`` leading recurrent blocks, then
``L // 3`` scanned super-blocks of (attention, recurrent, recurrent) — this
cyclic rotation reproduces the paper's r,r,a,r,r,a,... sequence exactly.

Training/prefill runs the RG-LRU with ``lax.associative_scan`` (log-depth);
the Pallas kernel (``repro.kernels.rglru_scan``) is the TPU sequential-scan
target. Decode is the O(1) recurrence plus a rolling window KV cache.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx
from . import attention as attn
from .common import (
    NEG_INF,
    apply_rope,
    cross_entropy,
    embed_tokens,
    lm_logits,
    rms_norm,
)
from .knobs import DEFAULT_KNOBS
from .params import ParamSpec, stack
from .ssm import causal_conv, conv_step

RG_C = 8.0          # RG-LRU decay sharpness constant (Griffin §2.4)
LAMBDA_INIT = -4.6  # softplus(Λ)≈0.01 → per-step decay a ≈ exp(-0.08·r)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _gelu_ffn_spec(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ffn"), "scaled_normal"),
        "w_up": ParamSpec((d, f), ("embed", "ffn"), "scaled_normal"),
        "w_down": ParamSpec((f, d), ("ffn", "embed"), "scaled_normal"),
    }


def rec_block_spec(cfg: ModelConfig) -> dict:
    r = cfg.recurrent
    d, lru = cfg.d_model, r.lru_width
    nb = cfg.n_heads                      # block-diagonal gate blocks
    bs = lru // nb
    return {
        "ln1": ParamSpec((d,), ("embed",), "zeros"),
        "w_x": ParamSpec((d, lru), ("embed", "lru_width"), "scaled_normal"),
        "w_gate": ParamSpec((d, lru), ("embed", "lru_width"), "scaled_normal"),
        "conv": ParamSpec((r.conv1d_width, lru), (None, "lru_width"),
                          "scaled_normal"),
        "rg_a_w": ParamSpec((nb, bs, bs), ("act_heads", None, None),
                            "scaled_normal"),
        "rg_a_b": ParamSpec((lru,), ("lru_width",), "zeros"),
        "rg_x_w": ParamSpec((nb, bs, bs), ("act_heads", None, None),
                            "scaled_normal"),
        "rg_x_b": ParamSpec((lru,), ("lru_width",), "zeros"),
        "lam": ParamSpec((lru,), ("lru_width",), "const", LAMBDA_INIT),
        "w_out": ParamSpec((lru, d), ("lru_width", "embed"), "scaled_normal"),
        "ln2": ParamSpec((d,), ("embed",), "zeros"),
        "ffn": _gelu_ffn_spec(cfg),
    }


def attn_block_spec(cfg: ModelConfig) -> dict:
    return {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "attn": attn.attn_spec(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "ffn": _gelu_ffn_spec(cfg),
    }


def _layout(cfg: ModelConfig) -> Tuple[int, int]:
    head = cfg.n_layers % 3
    n_sb = cfg.n_layers // 3
    return head, n_sb


def model_spec(cfg: ModelConfig) -> dict:
    head, n_sb = _layout(cfg)
    v = cfg.padded_vocab()
    spec = {
        "embed": {"tok": ParamSpec((v, cfg.d_model), ("vocab", "embed"),
                                   "normal", 0.02)},
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
    }
    if head:
        spec["head_rec"] = stack(rec_block_spec(cfg), head)
    if n_sb:
        spec["sb"] = stack({"attn": attn_block_spec(cfg),
                            "rec": stack(rec_block_spec(cfg), 2)}, n_sb)
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((cfg.d_model, v), ("embed", "vocab"),
                                    "scaled_normal")
    return spec


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------

def _blockdiag(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, S, lru); w: (nb, bs, bs); b: (lru,)."""
    B, S, lru = x.shape
    nb, bs, _ = w.shape
    xb = x.reshape(B, S, nb, bs)
    y = jnp.einsum("bshi,hij->bshj", xb, w).reshape(B, S, lru)
    return y + b


def rglru_gates(p: dict, x: jax.Array):
    """x: (B, S, lru) post-conv. Returns (log_a f32, beta·x f32)."""
    r = jax.nn.sigmoid(_blockdiag(x, p["rg_a_w"], p["rg_a_b"]).astype(jnp.float32))
    i = jax.nn.sigmoid(_blockdiag(x, p["rg_x_w"], p["rg_x_b"]).astype(jnp.float32))
    log_a = -RG_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    bx = beta * i * x.astype(jnp.float32)
    return log_a, bx


def rglru_full(p: dict, x: jax.Array):
    """Linear recurrence over the sequence. Returns (h (B,S,lru), h_last)."""
    log_a, bx = rglru_gates(p, x)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2
    _, h = lax.associative_scan(combine, (jnp.exp(log_a), bx), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(p: dict, x: jax.Array, h_prev: jax.Array):
    """x: (B, 1, lru); h_prev: (B, lru) f32."""
    log_a, bx = rglru_gates(p, x)
    h = jnp.exp(log_a[:, 0]) * h_prev + bx[:, 0]
    return h.astype(x.dtype)[:, None], h


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _gelu_ffn(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.gelu(g) * u, p["w_down"])


def rec_block_full(cfg, p, x_res, ctx, collect=False):
    r = cfg.recurrent
    h = rms_norm(x_res, p["ln1"], cfg.norm_eps)
    gate = jax.nn.gelu(jnp.einsum("bsd,dl->bsl", h, p["w_gate"]))
    xr = jnp.einsum("bsd,dl->bsl", h, p["w_x"])
    conv_in = xr
    xr = causal_conv(xr, p["conv"])
    hr, h_last = rglru_full(p, xr)
    y = jnp.einsum("bsl,ld->bsd", hr * gate, p["w_out"])
    x_res = x_res + y
    h2 = rms_norm(x_res, p["ln2"], cfg.norm_eps)
    x_res = x_res + _gelu_ffn(p["ffn"], h2)
    state = None
    if collect:
        state = {"h": h_last,
                 "conv": conv_in[:, -(r.conv1d_width - 1):]}
    return x_res, state


def rec_block_step(cfg, p, x_res, cache, ctx):
    h = rms_norm(x_res, p["ln1"], cfg.norm_eps)
    gate = jax.nn.gelu(jnp.einsum("bsd,dl->bsl", h, p["w_gate"]))
    xr = jnp.einsum("bsd,dl->bsl", h, p["w_x"])
    y_conv, new_window = conv_step(cache["conv"], p["conv"], xr)
    hr, h_new = rglru_step(p, y_conv, cache["h"])
    y = jnp.einsum("bsl,ld->bsd", hr * gate, p["w_out"])
    x_res = x_res + y
    h2 = rms_norm(x_res, p["ln2"], cfg.norm_eps)
    x_res = x_res + _gelu_ffn(p["ffn"], h2)
    return x_res, {"h": h_new, "conv": new_window}


def attn_block_full(cfg, p, x_res, positions, ctx, knobs, collect=False):
    W = cfg.recurrent.attention_window
    h = rms_norm(x_res, p["ln1"], cfg.norm_eps)
    if collect:
        a, (k, v) = attn.attn_full(cfg, p["attn"], h, positions, ctx, knobs,
                                   window=W, return_kv=True)
        B, S = h.shape[:2]
        if S >= W:
            kw, vw = k[:, -W:], v[:, -W:]
        else:
            pad = [(0, 0)] * k.ndim
            pad[1] = (W - S, 0)
            kw, vw = jnp.pad(k, pad), jnp.pad(v, pad)
        state = {"k": kw, "v": vw}
    else:
        a = attn.attn_full(cfg, p["attn"], h, positions, ctx, knobs, window=W)
        state = None
    x_res = x_res + a
    h2 = rms_norm(x_res, p["ln2"], cfg.norm_eps)
    x_res = x_res + _gelu_ffn(p["ffn"], h2)
    return x_res, state


def attn_block_step(cfg, p, x_res, cache, pos, ctx):
    """Rolling (end-aligned) window cache: shift left, append at the end."""
    W = cfg.recurrent.attention_window
    B = x_res.shape[0]
    h = rms_norm(x_res, p["ln1"], cfg.norm_eps)
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    q, k, v = attn._qkv(cfg, p["attn"], h)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k_cache = jnp.concatenate([cache["k"][:, 1:], k.astype(cache["k"].dtype)], axis=1)
    v_cache = jnp.concatenate([cache["v"][:, 1:], v.astype(cache["v"].dtype)], axis=1)
    filled = jnp.minimum(pos + 1, W)
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    qh = (q * hd ** -0.5).reshape(B, KVH, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, k_cache,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(W)[None] >= (W - filled)             # (1, W)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", pr, v_cache,
                     preferred_element_type=jnp.float32)
    a = jnp.einsum("bsk,kd->bsd",
                   out.reshape(B, 1, H * hd).astype(h.dtype), p["attn"]["wo"])
    x_res = x_res + a
    h2 = rms_norm(x_res, p["ln2"], cfg.norm_eps)
    x_res = x_res + _gelu_ffn(p["ffn"], h2)
    return x_res, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Stack plumbing
# ---------------------------------------------------------------------------

def _tree_idx(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _stack_forward(cfg, params, x, positions, ctx, knobs, collect=False):
    head, n_sb = _layout(cfg)
    from .transformer import _remat
    head_states = []
    if head:
        for i in range(head):
            x, st = rec_block_full(cfg, _tree_idx(params["head_rec"], i),
                                   x, ctx, collect)
            head_states.append(st)

    sb_states = None
    if n_sb:
        def body(x, lp):
            x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
            x, a_st = attn_block_full(cfg, lp["attn_blk"], x, positions, ctx,
                                      knobs, collect)
            r_sts = []
            for i in range(2):
                x, r_st = rec_block_full(cfg, _tree_idx(lp["rec"], i), x,
                                         ctx, collect)
                r_sts.append(r_st)
            if collect:
                r_stack = jax.tree.map(lambda *z: jnp.stack(z), *r_sts)
                return x, (a_st, r_stack)
            return x, None

        sb_params = {"attn_blk": params["sb"]["attn"], "rec": params["sb"]["rec"]}
        body_fn = body if collect else _remat(body, knobs.remat)
        x, sb_states = lax.scan(body_fn, x, sb_params)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, head_states, sb_states


def _head_w(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"]["tok"].T
    return params["lm_head"]


def loss_fn(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            z_loss: float = 0.0):
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)          # gemma scaling
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, _, _ = _stack_forward(cfg, params, x, positions, ctx, knobs)
    head = _head_w(cfg, params)
    logits = lm_logits(x, head, cfg.vocab_size)
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"), z_loss)
    return ce, {"ce": ce, "moe_aux": jnp.float32(0.0)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def _rec_cache_zero(cfg, batch, dtype):
    r = cfg.recurrent
    return {"h": jnp.zeros((batch, r.lru_width), jnp.float32),
            "conv": jnp.zeros((batch, r.conv1d_width - 1, r.lru_width), dtype)}


def _attn_cache_zero(cfg, batch, dtype):
    W = cfg.recurrent.attention_window
    return {"k": jnp.zeros((batch, W, cfg.n_kv_heads, cfg.head_dim_), dtype),
            "v": jnp.zeros((batch, W, cfg.n_kv_heads, cfg.head_dim_), dtype)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    head, n_sb = _layout(cfg)
    z = lambda t, n: jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype), t)
    cache = {"pos": jnp.zeros((), jnp.int32),
             "lengths": jnp.zeros((batch,), jnp.int32)}
    if head:
        cache["head_rec"] = z(_rec_cache_zero(cfg, batch, dtype), head)
    if n_sb:
        cache["sb"] = {
            "attn": z(_attn_cache_zero(cfg, batch, dtype), n_sb),
            "rec": jax.tree.map(
                lambda a: jnp.zeros((n_sb, 2) + a.shape, a.dtype),
                _rec_cache_zero(cfg, batch, dtype)),
        }
    return cache


def cache_axes(cfg: ModelConfig) -> dict:
    head, n_sb = _layout(cfg)
    rec = {"h": ("layers", "cache_batch", "lru_width"),
           "conv": ("layers", "cache_batch", None, "lru_width")}
    axes = {"pos": (), "lengths": ("cache_batch",)}
    if head:
        axes["head_rec"] = rec
    if n_sb:
        axes["sb"] = {
            "attn": {"k": ("layers", "cache_batch", "cache_seq",
                           "cache_heads", None),
                     "v": ("layers", "cache_batch", "cache_seq",
                           "cache_heads", None)},
            "rec": {"h": ("layers", None, "cache_batch", "lru_width"),
                    "conv": ("layers", None, "cache_batch", None,
                             "lru_width")},
        }
    return axes


def prefill(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            cache_len=None):
    dtype = jnp.dtype(cfg.dtype)
    head, n_sb = _layout(cfg)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, head_states, sb_states = _stack_forward(cfg, params, x, positions,
                                               ctx, knobs, collect=True)
    logits = lm_logits(x[:, -1:], _head_w(cfg, params), cfg.vocab_size)
    cache = {"pos": jnp.int32(S), "lengths": jnp.full((B,), S, jnp.int32)}
    if head:
        cache["head_rec"] = jax.tree.map(lambda *z: jnp.stack(z), *head_states)
    if n_sb:
        a_st, r_st = sb_states
        cache["sb"] = {"attn": a_st, "rec": r_st}
    return logits[:, 0], cache


def decode_step(cfg, params, cache, batch, ctx=ShardCtx(),
                knobs=DEFAULT_KNOBS):
    dtype = jnp.dtype(cfg.dtype)
    head, n_sb = _layout(cfg)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    pos = cache["pos"]
    new_cache = {"pos": pos + 1, "lengths": cache["lengths"] + 1}

    if head:
        new_heads = []
        for i in range(head):
            x, st = rec_block_step(cfg, _tree_idx(params["head_rec"], i), x,
                                   _tree_idx(cache["head_rec"], i), ctx)
            new_heads.append(st)
        new_cache["head_rec"] = jax.tree.map(lambda *z: jnp.stack(z),
                                             *new_heads)
    if n_sb:
        def body(x, xs):
            lp, c_attn, c_rec = xs
            x, a_st = attn_block_step(cfg, lp["attn_blk"], x, c_attn, pos, ctx)
            r_sts = []
            for i in range(2):
                x, r_st = rec_block_step(cfg, _tree_idx(lp["rec"], i), x,
                                         _tree_idx(c_rec, i), ctx)
                r_sts.append(r_st)
            r_stack = jax.tree.map(lambda *z: jnp.stack(z), *r_sts)
            return x, (a_st, r_stack)

        sb_params = {"attn_blk": params["sb"]["attn"], "rec": params["sb"]["rec"]}
        x, (a_st, r_st) = lax.scan(
            body, x, (sb_params, cache["sb"]["attn"], cache["sb"]["rec"]))
        new_cache["sb"] = {"attn": a_st, "rec": r_st}
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x, _head_w(cfg, params), cfg.vocab_size)
    return logits[:, 0], new_cache
