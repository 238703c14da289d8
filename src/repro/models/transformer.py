"""Decoder-only LM stack covering the dense, MoE, MLA, and VLM assigned
architectures. One scan-over-layers implementation; per-arch behaviour is
driven entirely by ``ModelConfig``. The layers form one or two homogeneous
stacks (:func:`layer_stacks`): an MoE model's leading dense layers
(``MoEConfig.first_k_dense``) scan apart from its expert layers.

Shapes legend: B batch, S sequence, d d_model, H heads, KVH kv heads,
hd head dim, V (padded) vocab, L layers, P vision-prefix length.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx
from . import attention as attn
from .common import (
    cross_entropy,
    embed_tokens,
    lm_logits,
    rms_norm,
    swiglu,
)
from .knobs import DEFAULT_KNOBS, RunKnobs
from .moe import moe_block, moe_spec
from .params import ParamSpec, stack

VISION_GRID_W = 32


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------

def ffn_spec(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("embed", "ffn"), "scaled_normal"),
        "w_up": ParamSpec((d, f), ("embed", "ffn"), "scaled_normal"),
        "w_down": ParamSpec((f, d), ("ffn", "embed"), "scaled_normal"),
    }


def layer_stacks(cfg: ModelConfig) -> List[Tuple[str, int, bool]]:
    """``(params key, layers, is_moe)`` of each homogeneous stack, in
    order: ``dense_blocks`` (an MoE model's leading dense layers), then
    ``blocks``."""
    if cfg.moe is None:
        return [("blocks", cfg.n_layers, False)]
    k = cfg.moe.first_k_dense
    lead = [("dense_blocks", k, False)] if k else []
    return lead + [("blocks", cfg.n_layers - k, True)]


def block_spec(cfg: ModelConfig, is_moe: bool) -> dict:
    spec = {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        "attn": attn.attn_spec(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
    }
    if is_moe:
        spec["moe"] = moe_spec(cfg)
    else:
        spec["ffn"] = ffn_spec(cfg)
    return spec


def model_spec(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab()
    spec = {
        "embed": {"tok": ParamSpec((v, cfg.d_model), ("vocab", "embed"),
                                   "normal", 0.02)},
        **{key: stack(block_spec(cfg, is_moe), n)
           for key, n, is_moe in layer_stacks(cfg)},
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((cfg.d_model, v), ("embed", "vocab"),
                                    "scaled_normal")
    return spec


def _head(cfg: ModelConfig, params: dict) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"]["tok"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------

def build_positions(cfg: ModelConfig, B: int, S: int,
                    prefix: int = 0) -> jax.Array:
    """(B, S) standard positions, or (3, B, S) M-RoPE positions where the
    first ``prefix`` slots are vision patches laid out on a 2-D grid."""
    s = jnp.arange(S, dtype=jnp.int32)
    if cfg.vlm is None:
        return jnp.broadcast_to(s[None], (B, S))
    is_vis = s < prefix
    t = jnp.where(is_vis, 0, s)
    h = jnp.where(is_vis, s // VISION_GRID_W, s)
    w = jnp.where(is_vis, s % VISION_GRID_W, s)
    pos = jnp.stack([t, h, w])                           # (3, S)
    return jnp.broadcast_to(pos[:, None], (3, B, S))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat(f, mode: str):
    if mode == "none":
        return f
    if mode == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return jax.checkpoint(f, prevent_cse=False)


def _embed_inputs(cfg: ModelConfig, params: dict, batch: Dict, dtype):
    """Token (+ optional stub-frontend) embeddings. Returns (x, prefix_len)."""
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)
    prefix = 0
    if cfg.vlm is not None and "patches" in batch:
        patches = batch["patches"].astype(dtype)        # (B, P, d) stub
        x = jnp.concatenate([patches, x], axis=1)
        prefix = patches.shape[1]
    return x, prefix


def _ffn(cfg: ModelConfig, lp: dict, h: jax.Array, ctx: ShardCtx,
         is_moe: bool, dropless: bool):
    """The layer's feed-forward: (output, MoE aux loss)."""
    if is_moe:
        return moe_block(h, lp["moe"], cfg, ctx, dropless=dropless)[:2]
    return (swiglu(h, lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                   lp["ffn"]["w_down"]), jnp.float32(0.0))


def forward_hidden(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,                  # (B, S, d) embedded inputs
    positions: jax.Array,
    ctx: ShardCtx,
    knobs: RunKnobs,
    *,
    collect_kv: bool = False,
    remat: Optional[str] = None,
    dropless: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Run the block stacks. Returns (hidden, moe_aux_mean, kv per stack:
    ``{params key: kv stacked over its layers}``). ``dropless`` serves
    every token routed to an expert; training's default drops past the
    experts' capacity."""
    remat = knobs.remat if remat is None else remat
    auxes, kvs = [], {}
    for key, _, is_moe in layer_stacks(cfg):
        x, aux, kv = _run_stack(cfg, params[key], x, positions, ctx, knobs,
                                is_moe, collect_kv, remat, dropless)
        if is_moe:               # the mean over the expert layers alone
            auxes.append(aux)
        kvs[key] = kv
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    aux = jnp.concatenate(auxes).mean() if auxes else jnp.float32(0.0)
    return x, aux, kvs if collect_kv else None


def _run_stack(cfg, blocks, x, positions, ctx, knobs, is_moe, collect_kv,
               remat, dropless):
    def body(x, lp):
        x = ctx.constrain(x, ("act_batch", "act_seq", "act_embed"))
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if collect_kv:
            a, kv = attn.attn_full(cfg, lp["attn"], h, positions, ctx, knobs,
                                   return_kv=True)
        else:
            a = attn.attn_full(cfg, lp["attn"], h, positions, ctx, knobs)
            kv = None
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f, aux = _ffn(cfg, lp, h, ctx, is_moe, dropless)
        x = x + f
        ys = (aux, kv) if collect_kv else (aux, None)
        return x, ys

    scan_body = _remat(body, remat) if not collect_kv else body
    x, (aux, kv) = jax.lax.scan(scan_body, x, blocks)
    return x, aux, kv


def loss_fn(
    cfg: ModelConfig,
    params: dict,
    batch: Dict,
    ctx: ShardCtx = ShardCtx(),
    knobs: RunKnobs = DEFAULT_KNOBS,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict]:
    dtype = jnp.dtype(cfg.dtype)
    x, prefix = _embed_inputs(cfg, params, batch, dtype)
    B, S = x.shape[:2]
    positions = build_positions(cfg, B, S, prefix)
    hidden, aux, _ = forward_hidden(cfg, params, x, positions, ctx, knobs)
    if prefix:
        hidden = hidden[:, prefix:]
    head = _head(cfg, params)
    labels = batch["labels"]
    mask = batch.get("mask")
    logits = lm_logits(hidden, head, cfg.vocab_size)
    ce = cross_entropy(logits, labels, mask, z_loss)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    loss = ce + aux_w * aux
    return loss, {"ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

# An MoE model's cache also counts the (layer, held expert) products its
# decode steps ran: the weights they read, in units of one expert.
EXPERTS_READ = "experts_read"


def _with_counter(cfg: ModelConfig, cache: dict) -> dict:
    if cfg.moe is not None:
        cache[EXPERTS_READ] = jnp.zeros((), jnp.int32)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    """Per stack (``cache["layers"][params key]``), each layer's cache
    stacked over the stack's layers."""
    per_layer = attn.attn_cache_init(cfg, batch, max_seq, dtype)
    stacked = {key: jax.tree.map(
        lambda x: jnp.zeros((n,) + x.shape, x.dtype), per_layer)
        for key, n, _ in layer_stacks(cfg)}
    return _with_counter(cfg, {"layers": stacked,
                               "pos": jnp.zeros((), jnp.int32),
                               "lengths": jnp.zeros((batch,), jnp.int32)})


def cache_axes(cfg: ModelConfig) -> dict:
    layer = jax.tree.map(lambda a: ("layers",) + a, attn.attn_cache_axes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    axes = {"layers": {key: layer for key, _, _ in layer_stacks(cfg)},
            "pos": (),
            "lengths": ("cache_batch",)}
    if cfg.moe is not None:
        axes[EXPERTS_READ] = ()
    return axes


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: Dict,
    ctx: ShardCtx = ShardCtx(),
    knobs: RunKnobs = DEFAULT_KNOBS,
    cache_len: Optional[int] = None,
) -> Tuple[jax.Array, dict]:
    """Full-sequence forward; returns (last-token logits, populated cache)."""
    dtype = jnp.dtype(cfg.dtype)
    x, prefix = _embed_inputs(cfg, params, batch, dtype)
    B, S = x.shape[:2]
    positions = build_positions(cfg, B, S, prefix)
    hidden, _, kv = forward_hidden(cfg, params, x, positions, ctx, knobs,
                                   collect_kv=True, remat="none",
                                   dropless=True)
    logits = lm_logits(hidden[:, -1:], _head(cfg, params), cfg.vocab_size)
    max_seq = cache_len or S
    layers = {key: jax.vmap(lambda kv_l: attn.attn_cache_from_prefill(
        cfg, kv_l, max_seq))(kv_s) for key, kv_s in kv.items()}
    cache = {"layers": layers,
             "pos": jnp.int32(S),
             "lengths": jnp.full((B,), S, jnp.int32)}
    return logits[:, 0], _with_counter(cfg, cache)


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    batch: Dict,
    ctx: ShardCtx = ShardCtx(),
    knobs: RunKnobs = DEFAULT_KNOBS,
) -> Tuple[jax.Array, dict]:
    """One token for every sequence. batch = {"tokens": (B, 1)}.

    An expert stack's routed weights stay out of the layer scan's inputs:
    the body gets the layer's index and the MoE layer reads each expert it
    needs at ``(layer, expert)`` of the stacked weights, so no layer's
    whole slice of them is copied out."""
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"]["tok"], batch["tokens"], dtype)  # (B,1,d)
    pos, lengths = cache["pos"], cache["lengths"] + 1
    window = (cfg.recurrent.attention_window
              if (cfg.attention_kind == "local" and cfg.recurrent) else None)

    def block(x, lp, cache_l, ffn):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, new_cache_l = attn.attn_decode(cfg, lp["attn"], h, cache_l,
                                          pos, lengths, ctx, window=window)
        x = x + a
        f, n_read = ffn(rms_norm(x, lp["ln2"], cfg.norm_eps))
        return x + f, (new_cache_l, n_read)

    def dense_body(x, xs):
        lp, cache_l = xs
        f = lp["ffn"]
        x, (new_cache_l, _) = block(x, lp, cache_l, lambda h: (
            swiglu(h, f["w_gate"], f["w_up"], f["w_down"]), None))
        return x, new_cache_l

    def moe_body(experts):
        def body(x, xs):
            lp, cache_l, layer = xs

            def ffn(h):
                y, _, n_read = moe_block(h, {**lp["moe"], **experts}, cfg,
                                         ctx, layer=layer)
                return y, n_read
            return block(x, lp, cache_l, ffn)
        return body

    new_layers, read = {}, None
    for key, n, is_moe in layer_stacks(cfg):
        if not is_moe:
            x, new_layers[key] = jax.lax.scan(
                dense_body, x, (params[key], cache["layers"][key]))
            continue
        lp = params[key]
        experts = {w: lp["moe"][w] for w in ("w_gate", "w_up", "w_down")}
        lp = {**lp, "moe": {k: v for k, v in lp["moe"].items()
                            if k not in experts}}
        x, (new_layers[key], n_read) = jax.lax.scan(
            moe_body(experts), x, (lp, cache["layers"][key], np.arange(n)))
        read = n_read.sum()
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = lm_logits(x, _head(cfg, params), cfg.vocab_size)
    new_cache = {"layers": new_layers, "pos": pos + 1, "lengths": lengths}
    if read is not None:
        new_cache[EXPERTS_READ] = cache[EXPERTS_READ] + read
    return logits[:, 0], new_cache
