"""DeepSeek-V2 decoder reference (DeepSeek-V2-Lite layout), float32.

Follows HF ``modeling_deepseek.py`` (``DeepseekV2ForCausalLM``) at
inference. Per layer: RMSNorm; multi-head latent attention with the query
from one full-rank projection (``q_lora_rank`` null), the keys and values
from a 512-rank latent (``kv_a_proj_with_mqa`` → ``kv_a_layernorm`` →
``kv_b_proj``) plus one 64-dim rope key shared by all heads, YaRN rope on
the rope dims after de-interleaving them, and the softmax scale
``q_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``; residual;
RMSNorm; for the first ``first_k_dense_replace`` layers a dense SwiGLU,
for the rest the expert layer: a float32 softmax router over all routed
experts, greedy top-k, the weights not renormalised when ``norm_topk_prob``
is false (else renormalised) and times ``routed_scaling_factor``, the
weighted sum of the experts' SwiGLUs, plus the shared experts (one SwiGLU
of ``n_shared_experts * moe_intermediate_size``); residual. A final RMSNorm
and the untied head.

One chip's share of an expert-parallel deployment (the configuration's
``deployment``): the router keeps its ``router_experts`` outputs and its
top-k over all of them, and only the held experts (ids
``first_held_expert`` on, ``n_routed_experts`` of them) add their part;
the rows of the vocabulary are the held slice. What absent experts would
add is left out, as on the chip that holds this share.

Departures from HF, none of which changes the function computed:

- ``kv_b_proj`` is stored as two matrices, ``w_uk`` (latent → per-head
  no-rope keys) and ``w_uv`` (latent → per-head values), HF's one matrix
  split by its output rows; ``kv_a_proj_with_mqa`` is ``w_dkv``.
- Norm weights are stored as offsets from 1, the served convention.
- HF's ``DeepseekV2MoE`` takes ``moe_infer`` (sorted tokens) at inference;
  here each held expert runs on every token and is weighted by the token's
  gate for it, 0 where it was not chosen: the same sum.

The forward pass runs one request at a time (``lax.map``) and one held
expert at a time, so that its activations fit beside the weights.
"""
from __future__ import annotations

import math
from typing import List

from .weights import Spec

DENSE, MOE = "['dense_blocks']", "['blocks']"


def _sizes(cfg: dict):
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return H, nope, rope, cfg["v_head_dim"], cfg["kv_lora_rank"]


def _padded_vocab(cfg: dict) -> int:
    return cfg.get("padded_vocab_size", cfg["vocab_size"])


def specs(cfg: dict) -> List[Spec]:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, nope, rope, vd, r = _sizes(cfg)
    if cfg["q_lora_rank"] is not None:
        raise ValueError("the reference computes the query without LoRA")
    k = cfg["first_k_dense_replace"]
    E, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    attn = [("['ln1']", (d,), "zeros"),
            ("['attn']['wq']", (d, H * (nope + rope)), "scaled_normal"),
            ("['attn']['w_dkv']", (d, r + rope), "scaled_normal"),
            ("['attn']['kv_norm']", (r,), "zeros"),
            ("['attn']['w_uk']", (r, H * nope), "scaled_normal"),
            ("['attn']['w_uv']", (r, H * vd), "scaled_normal"),
            ("['attn']['wo']", (H * vd, d), "scaled_normal"),
            ("['ln2']", (d,), "zeros")]
    f = cfg["intermediate_size"]
    dense = attn + [("['ffn']['w_gate']", (d, f), "scaled_normal"),
                    ("['ffn']['w_up']", (d, f), "scaled_normal"),
                    ("['ffn']['w_down']", (f, d), "scaled_normal")]
    moe = attn + [
        ("['moe']['router']", (d, cfg["deployment"]["router_experts"]),
         "scaled_normal"),
        ("['moe']['w_gate']", (E, d, fe), "scaled_normal"),
        ("['moe']['w_up']", (E, d, fe), "scaled_normal"),
        ("['moe']['w_down']", (E, fe, d), "scaled_normal")]
    if fs:
        moe += [("['moe']['shared']['w_gate']", (d, fs), "scaled_normal"),
                ("['moe']['shared']['w_up']", (d, fs), "scaled_normal"),
                ("['moe']['shared']['w_down']", (fs, d), "scaled_normal")]
    V = _padded_vocab(cfg)
    out = [("['embed']['tok']", (V, d), "normal", 0.02)]
    if k:
        out += [(f"{DENSE}{p}", (k,) + s, init, 0.02) for p, s, init in dense]
    out += [(f"{MOE}{p}", (L - k,) + s, init, 0.02) for p, s, init in moe]
    out += [("['ln_f']", (d,), "zeros", 0.02),
            ("['lm_head']", (d, V), "scaled_normal", 0.02)]
    return out


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) / (
        2 * math.log(base))


def rope_tables(cfg: dict):
    """``(inv_freq (rope/2,), cos/sin factor, softmax scale)``, as HF's
    ``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention`` set
    them."""
    import jax.numpy as jnp

    H, nope, rope, _vd, _r = _sizes(cfg)
    base = cfg["rope_theta"]
    dims = jnp.arange(0, rope, 2, dtype=jnp.float32) / rope
    freq_extra = 1.0 / (base ** dims)
    scale = (nope + rope) ** -0.5
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq_extra, 1.0, scale
    if rs["type"] != "yarn":
        raise ValueError(f"unknown rope_scaling {rs['type']!r}")
    factor = rs["factor"]
    freq_inter = 1.0 / (factor * base ** dims)
    orig = rs["original_max_position_embeddings"]
    low = max(math.floor(_correction_dim(rs["beta_fast"], rope, base, orig)), 0)
    high = min(math.ceil(_correction_dim(rs["beta_slow"], rope, base, orig)),
               rope - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    mscale = (yarn_get_mscale(factor, rs["mscale"])
              / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    if rs.get("mscale_all_dim"):
        m = yarn_get_mscale(factor, rs["mscale_all_dim"])
        scale = scale * m * m
    return inv_freq, mscale, scale


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def logits(cfg: dict, p: dict, tokens, first: int, count: int, mm):
    """``(B, count, vocab_size)`` logits at positions ``first`` to
    ``first + count - 1`` of ``tokens`` ``(B, S)``."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    eps = cfg["rms_norm_eps"]
    H, nope, rope, vd, r = _sizes(cfg)
    S = tokens.shape[1]
    inv_freq, mscale, softmax_scale = rope_tables(cfg)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([freqs, freqs], -1)             # (S, rope)
    cos, sin = jnp.cos(emb) * mscale, jnp.sin(emb) * mscale
    causal = jnp.tril(jnp.ones((S, S), bool))
    k_top = cfg["num_experts_per_tok"]
    dep = cfg["deployment"]
    held = dep["first_held_expert"] + jnp.arange(cfg["n_routed_experts"])

    def rotate_half(t):
        t1, t2 = t[..., :rope // 2], t[..., rope // 2:]
        return jnp.concatenate([-t2, t1], -1)

    def apply_rope(t):                                    # (S, ..., rope)
        t = jnp.concatenate([t[..., 0::2], t[..., 1::2]], -1)  # de-interleave
        c = cos.reshape((S,) + (1,) * (t.ndim - 2) + (rope,))
        s = sin.reshape((S,) + (1,) * (t.ndim - 2) + (rope,))
        return t * c + rotate_half(t) * s

    def swiglu(h, wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    def attention(x, lp):
        h = rms_norm(x, lp["['ln1']"], eps)
        q = mm(h, lp["['attn']['wq']"]).reshape(S, H, nope + rope)
        q_nope, q_pe = q[..., :nope], apply_rope(q[..., nope:])
        ckv = mm(h, lp["['attn']['w_dkv']"])
        c = rms_norm(ckv[:, :r], lp["['attn']['kv_norm']"], eps)
        k_pe = apply_rope(ckv[:, r:])                     # (S, rope)
        k_nope = mm(c, lp["['attn']['w_uk']"]).reshape(S, H, nope)
        v = mm(c, lp["['attn']['w_uv']"]).reshape(S, H, vd)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=hi)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe, precision=hi))
        a = jax.nn.softmax(jnp.where(causal, s * softmax_scale, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", a, v, precision=hi).reshape(S, H * vd)
        return x + mm(o, lp["['attn']['wo']"])

    def dense_layer(x, lp):
        x = attention(x, lp)
        h = rms_norm(x, lp["['ln2']"], eps)
        return x + swiglu(h, lp["['ffn']['w_gate']"], lp["['ffn']['w_up']"],
                          lp["['ffn']['w_down']"]), None

    def moe_layer(x, lp):
        x = attention(x, lp)
        h = rms_norm(x, lp["['ln2']"], eps)
        scores = jax.nn.softmax(mm(h, lp["['moe']['router']"]), -1)  # (S, E)
        top_w, top_i = jax.lax.top_k(scores, k_top)
        if cfg["norm_topk_prob"]:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        top_w = top_w * cfg["routed_scaling_factor"]
        gate = ((top_i[:, :, None] == held[None, None]) * top_w[:, :, None]
                ).sum(1)                                   # (S, held)

        def expert(y, e):
            wg, wu, wd, g = e
            return y + g[:, None] * swiglu(h, wg, wu, wd), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
            lp["['moe']['w_gate']"], lp["['moe']['w_up']"],
            lp["['moe']['w_down']"], gate.T))
        if cfg["n_shared_experts"]:
            y = y + swiglu(h, lp["['moe']['shared']['w_gate']"],
                           lp["['moe']['shared']['w_up']"],
                           lp["['moe']['shared']['w_down']"])
        return x + y, None

    def stack(pre):
        return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

    def one(seq):
        x = p["['embed']['tok']"][seq]
        if cfg["first_k_dense_replace"]:
            x, _ = jax.lax.scan(dense_layer, x, stack(DENSE))
        x, _ = jax.lax.scan(moe_layer, x, stack(MOE))
        x = rms_norm(x[first:first + count], p["['ln_f']"], eps)
        return mm(x, p["['lm_head']"])[..., :cfg["vocab_size"]]

    return jax.lax.map(one, tokens)
