"""Shared model components: norms, RoPE/M-RoPE, embeddings, GQA attention
(memory-bounded chunked implementation), SwiGLU, losses.

The chunked attention here is the *default execution path* of the framework
(pure JAX, flash-style online softmax, compiles to bounded-memory HLO on any
backend). The Pallas kernels in ``repro.kernels`` are the TPU-target
implementations of the same contract and are validated against
``repro.kernels.ref`` oracles; select them with ``use_kernels=True``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq=None, mscale: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. ``inv_freq`` (D/2,)
    replaces the plain frequencies of ``theta`` (YaRN), and ``mscale``
    scales cos and sin."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if inv_freq is None else inv_freq  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv         # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Multimodal RoPE (qwen2-vl). positions: (3, B, S) for (t, h, w);
    ``sections`` split the D/2 frequency dims across the three position ids."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                   # (D/2,)
    assert sum(sections) == d // 2, (sections, d)
    # Select, per frequency index, which of the 3 position streams drives it.
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=d // 2)             # (D/2,)
    # (B, S, D/2): pick positions[sec_ids[i]] for dim i
    pos3 = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)    # (B, S, 3)
    pos_per_dim = jnp.take(pos3, sec_ids, axis=-1)               # (B, S, D/2)
    ang = pos_per_dim * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — memory-bounded chunked (flash-style) implementation
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, KVH, G, D); k: (B, Sk, KVH, D) -> (B, KVH, G, Sq, Sk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_context(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (B, KVH, G, Sq, Sk); v: (B, Sk, KVH, D) -> (B, Sq, KVH, G, D)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                      preferred_element_type=jnp.float32)


def chunked_attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Sk, KVH, D)
    v: jax.Array,            # (B, Sk, KVH, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,     # sliding-window (local) attention
    q_offset: int = 0,                # absolute position of q[0] (for caches)
    q_block: int = 1024,
    kv_block: int = 1024,
    softmax_scale: Optional[float] = None,
    unroll: bool = False,
) -> jax.Array:
    """Flash-style attention: outer scan over q blocks, inner scan over kv
    blocks with online softmax. Peak temp ~ (B, KVH, G, q_block, kv_block).

    ``unroll=True`` replaces the scans with python loops — used ONLY by the
    roofline analysis lowerings (XLA cost_analysis counts while bodies once,
    which would undercount attention by n_q·n_k)."""
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    # pad to block multiples (masked out below)
    pad_q = (-Sq) % q_block
    pad_k = (-Sk) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    n_q, n_k = Sq_p // q_block, Sk_p // kv_block

    qr = (q * scale).reshape(B, n_q, q_block, KVH, G, D)
    kr = k.reshape(B, n_k, kv_block, KVH, D)
    vr = v.reshape(B, n_k, kv_block, KVH, Dv)

    q_pos_base = jnp.arange(q_block) + q_offset
    k_pos_base = jnp.arange(kv_block)

    def q_step(_, qi):
        qb = qr[:, qi]                                       # (B,qb,KVH,G,D)
        q_pos = q_pos_base + qi * q_block                    # absolute positions

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb = kr[:, ki], vr[:, ki]
            k_pos = k_pos_base + ki * kv_block
            s = _gqa_scores(qb, kb)                          # (B,KVH,G,qb,kb) f32
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            mask &= (k_pos < Sk)[None, :]                    # kv padding
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))           # (B,KVH,G,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            corr_q = jnp.moveaxis(corr, -1, 1)[..., None]    # (B,qb,KVH,G,1)
            acc_new = acc * corr_q + _gqa_context(p, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, q_block, KVH, G, Dv), jnp.float32)
        carry = (m0, l0, a0)
        if unroll:
            for ki in range(n_k):
                carry, _ = kv_step(carry, ki)
            m, l, acc = carry
        else:
            (m, l, acc), _ = lax.scan(kv_step, carry, jnp.arange(n_k))
        l = jnp.moveaxis(l, -1, 1)[..., None]                # (B,qb,KVH,G,1)
        out = acc / jnp.maximum(l, 1e-30)
        return None, out.astype(q.dtype)

    if unroll:
        blocks = jnp.stack([q_step(None, qi)[1] for qi in range(n_q)])
    else:
        _, blocks = lax.scan(q_step, None, jnp.arange(n_q))  # (n_q,B,qb,…)
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, Sq_p, H, Dv)
    return out[:, :Sq]


def decode_attention(
    q: jax.Array,            # (B, 1, H, D)
    k_cache: jax.Array,      # (B, S, KVH, D)
    v_cache: jax.Array,      # (B, S, KVH, D)
    lengths: jax.Array,      # (B,) number of valid cache entries (incl. new)
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) KV cache."""
    B, _, H, D = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qh = (q * scale).reshape(B, KVH, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, k_cache,
                   preferred_element_type=jnp.float32)        # (B,KVH,G,S)
    pos = jnp.arange(S)[None]                                 # (1, S)
    mask = pos < lengths[:, None]
    if window is not None:
        mask &= pos >= (lengths[:, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# FFN / embeddings / loss
# ---------------------------------------------------------------------------

def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def embed_tokens(table: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(dtype)


def lm_logits(x: jax.Array, head: jax.Array, vocab_size: int) -> jax.Array:
    """x: (B, S, d); head: (d, V_pad). Padded vocab columns are masked."""
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    v_pad = head.shape[-1]
    if v_pad > vocab_size:
        pad_mask = jnp.arange(v_pad) >= vocab_size
        logits = jnp.where(pad_mask[None, None], NEG_INF, logits)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  z_loss: float = 0.0) -> jax.Array:
    """Mean token cross-entropy. logits f32 (B,S,V_pad); labels (B,S) int."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        return nll.mean()
    mask = mask.astype(nll.dtype)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_cross_entropy(x: jax.Array, head: jax.Array, labels: jax.Array,
                          vocab_size: int, mask: Optional[jax.Array] = None,
                          z_loss: float = 0.0, chunk: int = 512,
                          unroll: bool = False) -> jax.Array:
    """Beyond-paper memory optimization: compute logits + CE per sequence
    chunk inside a scan so the (B, S, V) logits tensor is never materialized.
    Used when the sharding config enables ``chunked_loss``."""
    B, S, d = x.shape
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        m = jnp.pad(mask if mask is not None else jnp.ones((B, S), jnp.float32),
                    ((0, 0), (0, pad)))
    else:
        m = mask if mask is not None else jnp.ones((B, S), jnp.float32)
    n = (S + pad) // chunk
    xr = x.reshape(B, n, chunk, d)
    lr = labels.reshape(B, n, chunk)
    mr = m.reshape(B, n, chunk)

    def step(carry, i):
        tot, cnt = carry
        logits = lm_logits(xr[:, i], head, vocab_size)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lr[:, i][..., None], axis=-1)[..., 0]
        nll = lse - gold
        if z_loss:
            nll = nll + z_loss * jnp.square(lse)
        mi = mr[:, i].astype(nll.dtype)
        return (tot + (nll * mi).sum(), cnt + mi.sum()), None

    carry = (jnp.float32(0.0), jnp.float32(0.0))
    if unroll:
        for i in range(n):
            carry, _ = step(carry, i)
        tot, cnt = carry
    else:
        (tot, cnt), _ = lax.scan(step, carry, jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)
