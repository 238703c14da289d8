"""Mixture-of-experts layer.

One core (:func:`_moe_core`) serves both execution paths. It is told which
routed experts it holds (``e_first`` and the leading axis of the expert
weights), routes every token over all ``n_experts``, computes the part of
the result its own experts give, and adds the shared experts:

- **EP path** (production): wrapped in ``shard_map``; experts are sharded
  over the ``model`` mesh axis (expert parallelism), tokens are replicated
  over ``model`` and sharded over batch axes. Each rank computes its local
  experts and the partial outputs are ``psum``-combined — the
  TPU-idiomatic equivalent of the all-to-all in GPU MoE systems.
- **Local path**: no collectives. The experts held are those of the
  config (``MoEConfig.experts_held`` from ``expert_first``; all of them
  by default): a chip's share of an expert-parallel deployment runs this
  path, and its partial result is what goes on to the next layer.

Serving is dropless, and its route depends on the number of (token, held
expert) pairs, ``T * top_k``, a static shape:

- above ``LOOP_MAX_PAIRS`` (a prefill) the pairs are sorted by expert and
  multiplied as one grouped product per weight (``lax.ragged_dot``);
- at most ``LOOP_MAX_PAIRS`` (a decode step) a loop runs over the held
  experts that got a pair, and only those experts' weights are read. Each
  touched expert multiplies all the pairs (masked), ``T * top_k`` FLOP a
  weight byte, which stays below the chip's ratio of FLOPs to HBM bytes,
  so the loop is bound by the weights it reads. Given the layer-stacked
  weights and a layer index, it slices ``(layer, expert)`` itself, so no
  layer's whole expert slice is ever copied out.

Training may instead ask for the capacity path (per-expert buffers of
``capacity_factor`` slots, overflow dropped).

Returns the layer output, the Switch-style load-balancing auxiliary loss,
and the number of held experts whose weights the layer read.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx, spec_for
from .common import swiglu
from .params import ParamSpec


def moe_spec(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_held, m.d_ff_expert
    spec = {
        "router": ParamSpec((d, m.n_experts), ("embed", None), "scaled_normal"),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "ffn"), "scaled_normal"),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "ffn"), "scaled_normal"),
        "w_down": ParamSpec((e, f, d), ("experts", "ffn", "embed"), "scaled_normal"),
    }
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        spec["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "ffn"), "scaled_normal"),
            "w_up": ParamSpec((d, fs), ("embed", "ffn"), "scaled_normal"),
            "w_down": ParamSpec((fs, d), ("ffn", "embed"), "scaled_normal"),
        }
    return spec


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(factor * n_tokens * top_k / n_experts) + 1
    return max(c, top_k)


# Pair counts up to this take the loop over touched experts: v5e's ratio of
# FLOPs to HBM bytes (197e12 / 819e9) is ~240, so a product of this many
# rows stays bound by the expert weights it reads.
LOOP_MAX_PAIRS = 128


def _layer_weight(w, layer, e):
    """Expert ``e``'s matrix: ``w[e]`` of one layer's weights, or
    ``w[layer, e]`` of the layer-stacked ones (``layer`` not None)."""
    return w[e] if layer is None else w[layer, e]


def _combine(ys, tok, w, T):
    """Weight each pair's output by its gate and add it to its token, in
    float32."""
    y = jnp.zeros((T, ys.shape[-1]), jnp.float32)
    return y.at[tok].add(ys * w[:, None])


def _ragged_experts(xf, experts, layer, local_e, valid, top_w, k):
    """Every (token, held expert) pair, sorted by expert, through one
    grouped product per weight. Pairs routed elsewhere sort last, outside
    every group, and add nothing. Reads every held expert."""
    T = xf.shape[0]
    w_gate, w_up, w_down = (w if layer is None else w[layer]
                            for w in experts)
    E_loc = w_gate.shape[0]
    key = jnp.where(valid, local_e, E_loc)                         # (T*k,)
    order = jnp.argsort(key, stable=True)
    tok = order // k
    sizes = jnp.bincount(key, length=E_loc + 1)[:E_loc].astype(jnp.int32)
    xs = xf[tok]                                                   # (T*k, d)
    g = lax.ragged_dot(xs, w_gate, sizes)
    u = lax.ragged_dot(xs, w_up, sizes)
    ys = lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)         # (T*k, d)
    held = valid[order]
    w = jnp.where(held, top_w.reshape(-1)[order], 0.0)
    ys = jnp.where(held[:, None], ys.astype(jnp.float32), 0.0)
    return _combine(ys, tok, w, T), jnp.int32(E_loc)


def _looped_experts(xf, experts, layer, local_e, valid, top_w, k):
    """A loop over the held experts that got a pair, in expert order; each
    multiplies every pair with its three matrices, at the grouped
    product's dtypes, and keeps the rows of its own pairs. Experts with no
    pair are never read."""
    T, d = xf.shape
    E_loc = experts[0].shape[-3]
    key = jnp.where(valid, local_e, E_loc)                         # (T*k,)
    touched = jnp.bincount(key, length=E_loc + 1)[:E_loc] > 0
    n = touched.sum(dtype=jnp.int32)
    ids, = jnp.nonzero(touched, size=E_loc, fill_value=0)
    tok = jnp.arange(T * k) // k
    xs = xf[tok]                                                   # (T*k, d)

    def one(i, ys):
        e = ids[i]
        w_gate, w_up, w_down = (_layer_weight(w, layer, e) for w in experts)
        h = jax.nn.silu(xs @ w_gate) * (xs @ w_up)
        out = (h @ w_down).astype(jnp.float32)
        return jnp.where((key == e)[:, None], out, ys)

    ys = lax.fori_loop(0, n, one, jnp.zeros((T * k, d), jnp.float32))
    w = jnp.where(valid, top_w.reshape(-1), 0.0)
    return _combine(ys, tok, w, T), n


def _grouped_experts(xf, experts, layer, local_e, valid, top_w, k):
    """Dropless: ``(y float32, held experts read)``, by the route the pair
    count takes (module doc)."""
    route = (_looped_experts if xf.shape[0] * k <= LOOP_MAX_PAIRS
             else _ragged_experts)
    y, n_read = route(xf, experts, layer, local_e, valid, top_w, k)
    return y.astype(xf.dtype), n_read


def _capacity_experts(xf, w_gate, w_up, w_down, local_e, valid, top_w, k,
                      capacity):
    """Per-expert buffers of ``capacity`` slots; pairs past them drop."""
    T, d = xf.shape
    E_loc = w_gate.shape[0]
    safe_e = jnp.where(valid, local_e, 0)
    one_hot = jax.nn.one_hot(jnp.where(valid, local_e, E_loc),
                             E_loc + 1, dtype=jnp.int32)           # (T*k, E_loc+1)
    slot = (jnp.cumsum(one_hot, axis=0) - 1)[jnp.arange(T * k), safe_e]
    keep = valid & (slot < capacity)
    tok = jnp.arange(T * k) // k
    scat_e = jnp.where(keep, safe_e, E_loc)                        # OOB -> drop
    scat_s = jnp.where(keep, slot, 0)
    buf = jnp.zeros((E_loc, capacity, d), xf.dtype)
    buf = buf.at[scat_e, scat_s].add(xf[tok], mode="drop")

    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)

    # combine (gather + weighted sum over the k copies)
    y_copies = out_buf[scat_e.clip(0, E_loc - 1), scat_s]          # (T*k, d)
    w_copies = jnp.where(keep, top_w.reshape(-1), 0.0)
    y = (y_copies * w_copies[:, None].astype(y_copies.dtype)
         ).reshape(T, k, d).sum(axis=1)
    return y.astype(xf.dtype)   # combine on the wire in bf16, not f32


def _moe_core(
    xf: jax.Array,               # (T, d) local tokens
    p: dict,                     # moe params; expert weights (E_loc, ...),
    #                              or (L, E_loc, ...) with ``layer``
    *,
    cfg: ModelConfig,
    e_first,                     # first held expert id (int or traced)
    capacity_factor: Optional[float],  # None: dropless
    psum: Optional[Callable],    # combine fn over the expert axis, or None
    pmean_tokens: Optional[Callable],  # mean over batch shards for aux loss
    layer=None,                  # index into layer-stacked expert weights
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    m = cfg.moe
    T, d = xf.shape
    E, k = m.n_experts, m.top_k

    with jax.named_scope("moe.router"):
        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", xf, p["router"],
                       preferred_element_type=jnp.float32), axis=-1)  # (T, E)
        top_w, top_i = lax.top_k(gates, k)                         # (T, k)
        if m.norm_topk_prob:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        if m.routed_scaling_factor != 1.0:
            top_w = top_w * m.routed_scaling_factor

    with jax.named_scope("moe.experts"):
        experts = (p["w_gate"], p["w_up"], p["w_down"])
        E_loc = experts[0].shape[-3]
        local_e = top_i.reshape(-1) - e_first                      # (T*k,)
        valid = (local_e >= 0) & (local_e < E_loc)
        if capacity_factor is None:
            y, n_read = _grouped_experts(xf, experts, layer, local_e, valid,
                                         top_w, k)
        else:
            C = _capacity(T, k, E, capacity_factor)
            y = _capacity_experts(xf, *experts, local_e, valid, top_w, k, C)
            n_read = jnp.int32(E_loc)
        if psum is not None:
            y = psum(y)

    if "shared" in p:            # every chip computes it alike: once
        with jax.named_scope("moe.shared"):
            sh = p["shared"]
            y = y + swiglu(xf, sh["w_gate"], sh["w_up"], sh["w_down"])

    # ---- load-balance aux loss (Switch): E * sum_e f_e * p_e --------------
    assign = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32)     # top-1 assign
    f_e = assign.mean(axis=0)
    p_e = gates.mean(axis=0)
    aux = E * jnp.sum(f_e * p_e)
    if pmean_tokens is not None:
        aux = pmean_tokens(aux)
    return y.astype(xf.dtype), aux, n_read


def moe_block(
    x: jax.Array,                # (B, S, d)
    p: dict,                     # moe params
    cfg: ModelConfig,
    ctx: ShardCtx,
    *,
    dropless: bool = True,
    layer=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the MoE FFN: ``(y, aux loss, held experts read)``. Chooses EP
    (shard_map) vs local path from ctx. ``dropless=False`` (training) uses
    capacity buffers. With ``layer``, the expert weights in ``p`` are the
    whole stack's ``(L, E_loc, ...)``, read at that layer."""
    B, S, d = x.shape
    m = cfg.moe
    E = m.n_held
    capacity_factor = None if dropless else m.capacity_factor

    def local():
        y, aux, n_read = _moe_core(x.reshape(B * S, d), p, cfg=cfg,
                                   e_first=jnp.int32(m.expert_first),
                                   capacity_factor=capacity_factor, psum=None,
                                   pmean_tokens=None, layer=layer)
        return y.reshape(B, S, d), aux, n_read

    if not ctx.active or "model" not in ctx.mesh.axis_names:
        return local()

    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes["model"]
    if E % n_model != 0:
        # experts don't divide the model axis: fall back to replicated experts
        return local()

    E_loc = E // n_model
    if layer is not None:        # shard one layer's experts over the mesh
        p = dict(p, **{w: p[w][layer] for w in ("w_gate", "w_up", "w_down")})
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    x_spec = spec_for(("act_batch", None, None), x.shape, mesh, ctx.rules)
    experts = P("model", None, None)
    p_specs = jax.tree.map(lambda _: P(), p)
    p_specs.update(w_gate=experts, w_up=experts, w_down=experts)

    def inner(x_l, p_l):
        Bl, Sl, _ = x_l.shape
        e_first = m.expert_first + lax.axis_index("model") * E_loc
        psum = lambda y: lax.psum(y, "model")
        pmean = (lambda a: lax.pmean(a, batch_axes)) if batch_axes else None
        y, aux, n_read = _moe_core(x_l.reshape(Bl * Sl, d), p_l, cfg=cfg,
                                   e_first=e_first,
                                   capacity_factor=capacity_factor,
                                   psum=psum, pmean_tokens=pmean)
        # every rank's reads, summed over the expert axis
        return y.reshape(Bl, Sl, d), aux, lax.psum(n_read, "model")

    return shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p)
