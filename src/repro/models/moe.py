"""Mixture-of-experts layer.

Two execution paths sharing one core algorithm (scatter/gather token
dispatch with per-rank capacity — no giant one-hot dispatch einsums):

- **EP path** (production): wrapped in ``shard_map``; experts are sharded
  over the ``model`` mesh axis (expert parallelism), tokens are replicated
  over ``model`` and sharded over batch axes. Each rank dispatches only to
  its local experts and the partial outputs are ``psum``-combined — the
  TPU-idiomatic equivalent of the all-to-all in GPU MoE systems.
- **Local path** (single device / smoke tests): identical math with
  ``E_local == E`` and no collectives.

Returns the layer output plus the Switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..configs import ModelConfig
from ..sharding.rules import ShardCtx, spec_for
from .params import ParamSpec


def moe_spec(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_ff_expert
    return {
        "router": ParamSpec((d, e), ("embed", None), "scaled_normal"),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "ffn"), "scaled_normal"),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "ffn"), "scaled_normal"),
        "w_down": ParamSpec((e, f, d), ("experts", "ffn", "embed"), "scaled_normal"),
    }


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(factor * n_tokens * top_k / n_experts) + 1
    return max(c, top_k)


def _moe_core(
    xf: jax.Array,               # (T, d) local tokens
    router_w: jax.Array,         # (d, E)
    w_gate: jax.Array,           # (E_loc, d, f)
    w_up: jax.Array,
    w_down: jax.Array,           # (E_loc, f, d)
    *,
    cfg: ModelConfig,
    e_first: jax.Array,          # scalar: first local expert id
    psum: Optional[Callable],    # combine fn over the expert axis, or None
    pmean_tokens: Optional[Callable],  # mean over batch shards for aux loss
) -> Tuple[jax.Array, jax.Array]:
    m = cfg.moe
    T, d = xf.shape
    E, k = m.n_experts, m.top_k
    E_loc = w_gate.shape[0]
    C = _capacity(T, k, E, m.capacity_factor)

    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xf, router_w,
                   preferred_element_type=jnp.float32), axis=-1)  # (T, E)
    top_w, top_i = lax.top_k(gates, k)                             # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- dispatch to local experts (scatter into capacity buffer) --------
    flat_i = top_i.reshape(-1)                                     # (T*k,)
    local_e = flat_i - e_first
    valid = (local_e >= 0) & (local_e < E_loc)
    safe_e = jnp.where(valid, local_e, 0)
    one_hot = jax.nn.one_hot(jnp.where(valid, local_e, E_loc),
                             E_loc + 1, dtype=jnp.int32)           # (T*k, E_loc+1)
    slot = (jnp.cumsum(one_hot, axis=0) - 1)[jnp.arange(T * k), safe_e]
    keep = valid & (slot < C)
    tok = jnp.arange(T * k) // k
    scat_e = jnp.where(keep, safe_e, E_loc)                        # OOB -> drop
    scat_s = jnp.where(keep, slot, 0)
    buf = jnp.zeros((E_loc, C, d), xf.dtype)
    buf = buf.at[scat_e, scat_s].add(xf[tok], mode="drop")

    # ---- expert FFN (SwiGLU) ---------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)

    # ---- combine (gather + weighted sum over the k copies) ---------------
    y_copies = out_buf[scat_e.clip(0, E_loc - 1), scat_s]          # (T*k, d)
    w_copies = jnp.where(keep, top_w.reshape(-1), 0.0)
    y = (y_copies * w_copies[:, None].astype(y_copies.dtype)
         ).reshape(T, k, d).sum(axis=1)
    y = y.astype(xf.dtype)      # combine on the wire in bf16, not f32
    if psum is not None:
        y = psum(y)

    # ---- load-balance aux loss (Switch): E * sum_e f_e * p_e --------------
    assign = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32)     # top-1 assign
    f_e = assign.mean(axis=0)
    p_e = gates.mean(axis=0)
    aux = E * jnp.sum(f_e * p_e)
    if pmean_tokens is not None:
        aux = pmean_tokens(aux)
    return y.astype(xf.dtype), aux


def moe_block(
    x: jax.Array,                # (B, S, d)
    p: dict,                     # moe params
    cfg: ModelConfig,
    ctx: ShardCtx,
) -> Tuple[jax.Array, jax.Array]:
    """Apply the MoE FFN. Chooses EP (shard_map) vs local path from ctx."""
    B, S, d = x.shape
    m = cfg.moe
    E = m.n_experts

    if not ctx.active or "model" not in ctx.mesh.axis_names:
        xf = x.reshape(B * S, d)
        y, aux = _moe_core(
            xf, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            cfg=cfg, e_first=jnp.int32(0), psum=None, pmean_tokens=None)
        return y.reshape(B, S, d), aux

    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes["model"]
    if E % n_model != 0:
        # experts don't divide the model axis: fall back to replicated experts
        xf = x.reshape(B * S, d)
        y, aux = _moe_core(
            xf, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            cfg=cfg, e_first=jnp.int32(0), psum=None, pmean_tokens=None)
        return y.reshape(B, S, d), aux

    E_loc = E // n_model
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    x_spec = spec_for(("act_batch", None, None), x.shape, mesh, ctx.rules)

    def inner(x_l, router_w, w_gate, w_up, w_down):
        Bl, Sl, _ = x_l.shape
        xf = x_l.reshape(Bl * Sl, d)
        e_first = lax.axis_index("model") * E_loc
        psum = lambda y: lax.psum(y, "model")
        pmean = (lambda a: lax.pmean(a, batch_axes)) if batch_axes else None
        y, aux = _moe_core(xf, router_w, w_gate, w_up, w_down,
                           cfg=cfg, e_first=e_first, psum=psum,
                           pmean_tokens=pmean)
        return y.reshape(Bl, Sl, d), aux

    y, aux = shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
