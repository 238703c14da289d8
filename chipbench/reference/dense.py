"""Dense decoder reference (Qwen1.5 / Qwen2 layout), float32.

Per layer: RMSNorm, Q/K/V projections with bias, rotary embedding in the
rotate-half form, causal softmax attention over all heads, output
projection, residual; RMSNorm, SwiGLU feed-forward, residual. A final
RMSNorm and the head (the embedding, transposed, when tied). Norm weights
are stored as offsets from 1, the convention of the served weights.
Logits cover the first ``vocab_size`` rows; the rest of the padded table
is never a token.
"""
from __future__ import annotations

from typing import List

from .weights import Spec


def specs(cfg: dict) -> List[Spec]:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = d // H, cfg["intermediate_size"]
    out = [("['embed']['tok']", (cfg["padded_vocab_size"], d), "normal", 0.02)]
    blk = [("['ln1']", (d,), "zeros"),
           ("['attn']['wq']", (d, H * hd), "scaled_normal"),
           ("['attn']['wk']", (d, KVH * hd), "scaled_normal"),
           ("['attn']['wv']", (d, KVH * hd), "scaled_normal"),
           ("['attn']['wo']", (H * hd, d), "scaled_normal"),
           ("['ln2']", (d,), "zeros"),
           ("['ffn']['w_gate']", (d, f), "scaled_normal"),
           ("['ffn']['w_up']", (d, f), "scaled_normal"),
           ("['ffn']['w_down']", (f, d), "scaled_normal")]
    if cfg["qkv_bias"]:
        blk += [("['attn']['bq']", (H * hd,), "zeros"),
                ("['attn']['bk']", (KVH * hd,), "zeros"),
                ("['attn']['bv']", (KVH * hd,), "zeros")]
    out += [(f"['blocks']{p}", (L,) + s, init, 0.02) for p, s, init in blk]
    out.append(("['ln_f']", (d,), "zeros", 0.02))
    if not cfg["tie_word_embeddings"]:
        out.append(("['lm_head']", (d, cfg["padded_vocab_size"]),
                    "scaled_normal", 0.02))
    return out


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
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    B, S = tokens.shape
    x = p["['embed']['tok']"][tokens]
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                       / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(t):
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))
    pre = "['blocks']"
    layers = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

    def body(x, lp):
        h = rms_norm(x, lp["['ln1']"], eps)
        q = mm(h, lp["['attn']['wq']"])
        k = mm(h, lp["['attn']['wk']"])
        v = mm(h, lp["['attn']['wv']"])
        if cfg["qkv_bias"]:
            q = q + lp["['attn']['bq']"]
            k = k + lp["['attn']['bk']"]
            v = v + lp["['attn']['bv']"]
        q = rope(q.reshape(B, S, H, hd))
        k = jnp.repeat(rope(k.reshape(B, S, KVH, hd)), H // KVH, axis=2)
        v = jnp.repeat(v.reshape(B, S, KVH, hd), H // KVH, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / jnp.sqrt(
            jnp.float32(hd))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=hi)
        x = x + mm(o.reshape(B, S, H * hd), lp["['attn']['wo']"])
        h = rms_norm(x, lp["['ln2']"], eps)
        g = jax.nn.silu(mm(h, lp["['ffn']['w_gate']"]))
        x = x + mm(g * mm(h, lp["['ffn']['w_up']"]), lp["['ffn']['w_down']"])
        return x, None

    x, _ = jax.lax.scan(body, x, layers)
    x = rms_norm(x[:, first:first + count], p["['ln_f']"], eps)
    head = (p["['embed']['tok']"].T if cfg["tie_word_embeddings"]
            else p["['lm_head']"])
    return mm(x, head)[..., :cfg["vocab_size"]]
