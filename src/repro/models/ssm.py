"""Mamba-2 (SSD — state-space duality) blocks. Attention-free mixer.

Training/prefill uses the chunked SSD algorithm: within-chunk "attention
duality" (quadratic inside a small chunk) plus an inter-chunk state
recurrence carried by ``lax.scan`` — this is the same blocking structure the
Pallas kernel (``repro.kernels.ssd_scan``) implements for TPU VMEM.

Decode is the O(1) state recurrence: ``h = exp(dt·A)·h + dt·B⊗x``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx
from .common import (
    cross_entropy,
    embed_tokens,
    lm_logits,
    rms_norm,
)
from .knobs import DEFAULT_KNOBS
from .params import ParamSpec, stack


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------

def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    H = s.n_heads(cfg.d_model)
    return s, d_in, H, s.head_dim, s.n_groups * s.d_state


def block_spec(cfg: ModelConfig) -> dict:
    s, d_in, H, P, gn = _dims(cfg)
    d = cfg.d_model
    return {
        "ln": ParamSpec((d,), ("embed",), "zeros"),
        "w_z": ParamSpec((d, d_in), ("embed", "ssm_inner"), "scaled_normal"),
        "w_x": ParamSpec((d, d_in), ("embed", "ssm_inner"), "scaled_normal"),
        "w_B": ParamSpec((d, gn), ("embed", None), "scaled_normal"),
        "w_C": ParamSpec((d, gn), ("embed", None), "scaled_normal"),
        "w_dt": ParamSpec((d, H), ("embed", None), "scaled_normal"),
        "conv_x": ParamSpec((s.d_conv, d_in), (None, "ssm_inner"), "scaled_normal"),
        "conv_B": ParamSpec((s.d_conv, gn), (None, None), "scaled_normal"),
        "conv_C": ParamSpec((s.d_conv, gn), (None, None), "scaled_normal"),
        "A_log": ParamSpec((H,), (None,), "zeros"),
        "D": ParamSpec((H,), (None,), "ones"),
        "dt_bias": ParamSpec((H,), (None,), "zeros"),
        "gate_norm": ParamSpec((d_in,), ("ssm_inner",), "zeros"),
        "w_out": ParamSpec((d_in, d), ("ssm_inner", "embed"), "scaled_normal"),
    }


def model_spec(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab()
    return {
        "embed": {"tok": ParamSpec((v, cfg.d_model), ("vocab", "embed"),
                                   "normal", 0.02)},
        "blocks": stack(block_spec(cfg), cfg.n_layers),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "lm_head": ParamSpec((cfg.d_model, v), ("embed", "vocab"),
                             "scaled_normal"),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv (shift-sum form; SPMD-friendly, no conv primitive)
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """x: (B, S, C); kernel: (W, C). y[t] = sum_w k[w] * x[t - (W-1) + w]."""
    W = kernel.shape[0]
    out = x * kernel[W - 1]
    for w in range(W - 1):
        shift = W - 1 - w
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :-shift]
        out = out + shifted * kernel[w]
    return out


def conv_step(window: jax.Array, kernel: jax.Array, x_new: jax.Array):
    """window: (B, W-1, C) past inputs; x_new: (B, 1, C).
    Returns (y (B, 1, C), new window)."""
    full = jnp.concatenate([window, x_new], axis=1)         # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", full, kernel)[:, None]
    return y, full[:, 1:]


# ---------------------------------------------------------------------------
# SSD core (chunked scan)
# ---------------------------------------------------------------------------

def ssd_scan(
    x: jax.Array,        # (B, S, H, P)  — dt-scaled inputs
    a: jax.Array,        # (B, S, H)     — log decays (dt * A, negative)
    Bm: jax.Array,       # (B, S, H, N)
    Cm: jax.Array,       # (B, S, H, N)
    chunk: int,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,S,H,P), final state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // chunk
    xr = x.reshape(Bsz, nc, chunk, H, P)
    ar = a.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Br = Bm.reshape(Bsz, nc, chunk, H, N)
    Cr = Cm.reshape(Bsz, nc, chunk, H, N)

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(h_prev, ci):
        xq, aq, bq, cq = xr[:, ci], ar[:, ci], Br[:, ci], Cr[:, ci]
        a_cum = jnp.cumsum(aq, axis=1)                       # (B,q,H)
        # intra-chunk (dual "attention" form): decay(i<-j) = exp(acum_i - acum_j)
        scores = jnp.einsum("bihn,bjhn->bhij", cq, bq,
                            preferred_element_type=jnp.float32)
        decay = jnp.exp(a_cum[:, :, None] - a_cum[:, None, :]  # (B,i,j,H)
                        ).transpose(0, 3, 1, 2)               # (B,H,i,j)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        L = jnp.where(mask[None, None], scores * decay, 0.0)
        y_intra = jnp.einsum("bhij,bjhp->bihp", L, xq.astype(jnp.float32))
        # inter-chunk: y_i += (C_i · h_prev) * exp(acum_i)
        y_inter = jnp.einsum("bihn,bhpn->bihp", cq.astype(jnp.float32), h_prev)
        y_inter = y_inter * jnp.exp(a_cum)[..., None]
        # state update
        chunk_decay = jnp.exp(a_cum[:, -1])                  # (B,H)
        in_decay = jnp.exp(a_cum[:, -1:, :] - a_cum)         # (B,q,H)
        dh = jnp.einsum("bqhn,bqhp,bqh->bhpn", bq.astype(jnp.float32),
                        xq.astype(jnp.float32), in_decay)
        h_new = chunk_decay[:, :, None, None] * h_prev + dh
        return h_new, (y_intra + y_inter).astype(x.dtype)

    h_final, ys = lax.scan(step, h0, jnp.arange(nc))         # ys (nc,B,q,H,P)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S + pad, H, P)[:, :S]
    return y, h_final


def ssd_step(h: jax.Array, x: jax.Array, a: jax.Array, Bm: jax.Array,
             Cm: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrence. h (B,H,P,N); x (B,H,P); a (B,H);
    Bm/Cm (B,H,N). Returns (y (B,H,P), h_new)."""
    h_new = jnp.exp(a)[..., None, None] * h + jnp.einsum(
        "bhp,bhn->bhpn", x.astype(jnp.float32), Bm.astype(jnp.float32))
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Cm.astype(jnp.float32))
    return y.astype(x.dtype), h_new


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _proj_inputs(cfg: ModelConfig, p: dict, h: jax.Array):
    """Shared between full and step paths. h already normed."""
    s, d_in, H, P, gn = _dims(cfg)
    z = jnp.einsum("bsd,di->bsi", h, p["w_z"])
    x = jnp.einsum("bsd,di->bsi", h, p["w_x"])
    Bm = jnp.einsum("bsd,dn->bsn", h, p["w_B"])
    Cm = jnp.einsum("bsd,dn->bsn", h, p["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", h, p["w_dt"])
    return z, x, Bm, Cm, dt


def _gates(cfg, p, x, Bm, Cm, dt):
    """Post-conv activations + continuous-time discretization."""
    s, d_in, H, P, gn = _dims(cfg)
    Bsz, S = x.shape[:2]
    x = jax.nn.silu(x)
    Bm = jax.nn.silu(Bm)
    Cm = jax.nn.silu(Cm)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                  # (H,)
    a = dt * A                                                    # log decay
    xh = x.reshape(Bsz, S, H, P)
    x_dt = xh * dt[..., None].astype(xh.dtype)
    rep = H // s.n_groups
    Bh = jnp.repeat(Bm.reshape(Bsz, S, s.n_groups, s.d_state), rep, axis=2)
    Ch = jnp.repeat(Cm.reshape(Bsz, S, s.n_groups, s.d_state), rep, axis=2)
    return xh, x_dt, a, Bh, Ch


def block_full(cfg: ModelConfig, p: dict, x_res: jax.Array, ctx: ShardCtx,
               collect_state: bool = False):
    s, d_in, H, P, gn = _dims(cfg)
    h = rms_norm(x_res, p["ln"], cfg.norm_eps)
    z, x, Bm, Cm, dt = _proj_inputs(cfg, p, h)
    conv_in = jnp.concatenate([x, Bm, Cm], axis=-1)
    x = causal_conv(x, p["conv_x"])
    Bm = causal_conv(Bm, p["conv_B"])
    Cm = causal_conv(Cm, p["conv_C"])
    xh, x_dt, a, Bh, Ch = _gates(cfg, p, x, Bm, Cm, dt)
    y, h_final = ssd_scan(x_dt, a, Bh, Ch, chunk=s.chunk_size)
    y = y + p["D"][None, None, :, None] * xh                     # skip
    Bsz, S = x_res.shape[:2]
    y = y.reshape(Bsz, S, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    if collect_state:
        state = {"ssm": h_final,
                 "conv": conv_in[:, -(s.d_conv - 1):]}
        return x_res + out, state
    return x_res + out, None


def block_step(cfg: ModelConfig, p: dict, x_res: jax.Array, cache: dict,
               ctx: ShardCtx):
    """x_res: (B, 1, d). cache: {"ssm": (B,H,P,N), "conv": (B,W-1,C)}."""
    s, d_in, H, P, gn = _dims(cfg)
    h = rms_norm(x_res, p["ln"], cfg.norm_eps)
    z, x, Bm, Cm, dt = _proj_inputs(cfg, p, h)
    conv_in = jnp.concatenate([x, Bm, Cm], axis=-1)              # (B,1,C)
    kernel = jnp.concatenate([p["conv_x"], p["conv_B"], p["conv_C"]], axis=-1)
    y_conv, new_window = conv_step(cache["conv"], kernel, conv_in)
    x, Bm, Cm = jnp.split(y_conv, [d_in, d_in + gn], axis=-1)
    xh, x_dt, a, Bh, Ch = _gates(cfg, p, x, Bm, Cm, dt)
    y, h_new = ssd_step(cache["ssm"], x_dt[:, 0], a[:, 0], Bh[:, 0], Ch[:, 0])
    y = y[:, None] + p["D"][None, None, :, None] * xh
    Bsz = x_res.shape[0]
    y = y.reshape(Bsz, 1, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    return x_res + out, {"ssm": h_new, "conv": new_window}


# ---------------------------------------------------------------------------
# Model-level API
# ---------------------------------------------------------------------------

def loss_fn(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            z_loss: float = 0.0):
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)

    def body(x, lp):
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        x, _ = block_full(cfg, lp, x, ctx)
        return x, jnp.float32(0.0)

    from .transformer import _remat
    x, _ = lax.scan(_remat(body, knobs.remat), x, params["blocks"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x, params["lm_head"], cfg.vocab_size)
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"), z_loss)
    return ce, {"ce": ce, "moe_aux": jnp.float32(0.0)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    s, d_in, H, P, gn = _dims(cfg)
    L = cfg.n_layers
    return {
        "layers": {
            "ssm": jnp.zeros((L, batch, H, P, s.d_state), jnp.float32),
            "conv": jnp.zeros((L, batch, s.d_conv - 1, d_in + 2 * gn), dtype),
        },
        "pos": jnp.zeros((), jnp.int32),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig) -> dict:
    return {
        "layers": {
            "ssm": ("layers", "cache_batch", "act_heads", None, None),
            "conv": ("layers", "cache_batch", None, "ssm_inner"),
        },
        "pos": (),
        "lengths": ("cache_batch",),
    }


def prefill(cfg, params, batch, ctx=ShardCtx(), knobs=DEFAULT_KNOBS,
            cache_len=None):
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    B, S = batch["tokens"].shape

    def body(x, lp):
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        x, state = block_full(cfg, lp, x, ctx, collect_state=True)
        return x, state

    x, states = lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x[:, -1:], params["lm_head"], cfg.vocab_size)
    cache = {"layers": states,
             "pos": jnp.int32(S),
             "lengths": jnp.full((B,), S, jnp.int32)}
    return logits[:, 0], cache


def decode_step(cfg, params, cache, batch, ctx=ShardCtx(),
                knobs=DEFAULT_KNOBS):
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)

    def body(x, xs):
        lp, cache_l = xs
        x, new_cache_l = block_step(cfg, lp, x, cache_l, ctx)
        return x, new_cache_l

    x, new_layers = lax.scan(body, x, (params["blocks"], cache["layers"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x, params["lm_head"], cfg.vocab_size)
    return logits[:, 0], {"layers": new_layers, "pos": cache["pos"] + 1,
                          "lengths": cache["lengths"] + 1}
