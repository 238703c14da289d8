"""Federated averaging over funcX endpoints (paper §8: "Flox uses funcX to
train and deploy FL models on one or more remote computers").

This is where *gradient compression* belongs in a federated FaaS system:
the expensive links are the inter-endpoint (DCN/WAN) transfers, so model
deltas are compressed before leaving an endpoint:

- ``int8`` — per-tensor symmetric quantization (8× over f32, 4× over f32+zstd
  in practice), with **error feedback**: the quantization residual is kept
  endpoint-side and added to the next round's delta, so compression noise
  is unbiased over rounds (Seide et al. / EF-SGD).
- ``topk`` — magnitude sparsification (indices + values), also with error
  feedback.

The round trip runs through the real FaaS path: a registered ``local_train``
function executes on each endpoint (warm container holds the jitted step),
deltas come back as payloads/DataRefs, the coordinator aggregates.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Delta codecs (compression + error feedback)
# ---------------------------------------------------------------------------

def quantize_int8(delta: np.ndarray) -> Dict[str, Any]:
    scale = float(np.max(np.abs(delta)) / 127.0) if delta.size else 0.0
    if scale == 0.0:
        return {"kind": "int8", "q": np.zeros(delta.shape, np.int8),
                "scale": 0.0}
    q = np.clip(np.round(delta / scale), -127, 127).astype(np.int8)
    return {"kind": "int8", "q": q, "scale": scale}


def dequantize_int8(msg: Dict[str, Any]) -> np.ndarray:
    return msg["q"].astype(np.float32) * msg["scale"]


def sparsify_topk(delta: np.ndarray, frac: float) -> Dict[str, Any]:
    flat = delta.reshape(-1)
    k = max(int(len(flat) * frac), 1)
    idx = np.argpartition(np.abs(flat), -k)[-k:].astype(np.int32)
    return {"kind": "topk", "idx": idx, "val": flat[idx].astype(np.float32),
            "shape": list(delta.shape)}


def desparsify_topk(msg: Dict[str, Any]) -> np.ndarray:
    out = np.zeros(int(np.prod(msg["shape"])), np.float32)
    out[msg["idx"]] = msg["val"]
    return out.reshape(msg["shape"])


def compress_tree(delta_tree: Any, method: str = "int8",
                  topk_frac: float = 0.1,
                  error_state: Optional[Any] = None) -> Tuple[Any, Any]:
    """Compress a pytree of deltas. Returns (messages, new_error_state).
    Error feedback: encode (delta + carried_error); carry the residual."""
    leaves, treedef = jax.tree.flatten(delta_tree)
    err_leaves = (treedef.flatten_up_to(error_state)
                  if error_state is not None else [None] * len(leaves))
    msgs, new_err = [], []
    for leaf, err in zip(leaves, err_leaves):
        d = np.asarray(leaf, np.float32)
        if err is not None:
            d = d + err
        if method == "int8":
            m = quantize_int8(d)
            rec = dequantize_int8(m)
        elif method == "topk":
            m = sparsify_topk(d, topk_frac)
            rec = desparsify_topk(m)
        elif method == "none":
            m = {"kind": "none", "d": d}
            rec = d
        else:
            raise ValueError(method)
        msgs.append(m)
        new_err.append(d - rec)
    return (treedef.unflatten(msgs), treedef.unflatten(new_err))


def decompress_tree(msg_tree: Any) -> Any:
    def dec(m):
        if m["kind"] == "int8":
            return dequantize_int8(m)
        if m["kind"] == "topk":
            return desparsify_topk(m)
        return m["d"]
    return jax.tree.map(dec, msg_tree,
                        is_leaf=lambda x: isinstance(x, dict) and "kind" in x)


def compressed_bytes(msg_tree: Any) -> int:
    total = 0
    for m in jax.tree.leaves(
            msg_tree, is_leaf=lambda x: isinstance(x, dict) and "kind" in x):
        if m["kind"] == "int8":
            total += m["q"].nbytes + 4
        elif m["kind"] == "topk":
            total += m["idx"].nbytes + m["val"].nbytes
        else:
            total += m["d"].nbytes
    return total


# ---------------------------------------------------------------------------
# Endpoint-side funcX functions (module-level so the wire reference
# ``repro.train.fedavg:fedavg_local_train`` resolves on any endpoint)
# ---------------------------------------------------------------------------

def train_warmth_key(arch: str, seq: int) -> str:
    """Warmth key advertised for a jit-compiled train step (DESIGN.md §10).

    Same grammar as the serving fabric's jit keys so one routing mechanism
    covers both: ``jit/<arch>/train/b<seq>``."""
    return f"jit/{arch}/train/b{seq}"


# One jitted train step + opt state per arch, held across invocations by
# the worker process — the FL analogue of the serving fabric's jit cache.
_LOCAL_STATE: Dict[str, Any] = {}


def _local_env(arch: str, seq: int, batch: int) -> Dict[str, Any]:
    from ..compile_cache import enable_compile_cache
    from ..configs import TrainConfig, get_config
    from ..models import get_model
    from .train_step import make_train_step

    key = train_warmth_key(arch, seq)
    env = _LOCAL_STATE.get(key)
    if env is None:
        enable_compile_cache()
        cfg = get_config(arch)
        model = get_model(cfg)
        tc = TrainConfig(learning_rate=5e-3, warmup_steps=0,
                         total_steps=200)
        env = {"cfg": cfg, "model": model,
               "step": jax.jit(make_train_step(model, tc)),
               "seq": seq, "batch": batch}
        _LOCAL_STATE[key] = env
    return env


def fedavg_local_train(data: Dict[str, Any]) -> Dict[str, Any]:
    """Registered FL client: run ``steps`` local SGD steps from the global
    ``params`` on a synthetic shard, return the raw f32 delta pytree.

    Payload: {"arch", "params", "seed", "steps", "seq"?, "batch"?}. The
    jitted step lives in the module-global ``_LOCAL_STATE``, so repeat
    rounds on the same worker skip the ``jax.jit`` compile — the warmth
    the coordinator's ``warmth_key`` routes toward."""
    from .data import SyntheticLM
    from .optimizer import init_opt_state

    arch = data["arch"]
    seq = int(data.get("seq", 8))
    batch = int(data.get("batch", 8))
    env = _local_env(arch, seq, batch)
    params = jax.tree.map(jnp.asarray, data["params"])
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.zeros((), jnp.int32)}
    ds = SyntheticLM(env["cfg"].vocab_size, seq, batch,
                     seed=int(data["seed"]))
    loss = 0.0
    for _, b in zip(range(int(data["steps"])), ds):
        state, m = env["step"](state, {k: jnp.asarray(v)
                                       for k, v in b.items()})
        loss = float(m["loss"])
    delta = jax.tree.map(
        lambda n, p: (np.asarray(n, np.float32)
                      - np.asarray(p, np.float32)), state["params"], params)
    return {"delta": delta, "loss": loss}


def fedavg_aggregate(data: Dict[str, Any]) -> Dict[str, Any]:
    """Registered aggregator: mean the client deltas (fetched peer-direct
    as DataRefs by the data plane before this runs), compress the mean
    once, and return the small message tree — the coordinator never sees
    a raw delta. Payload: {"parts": [{"delta", "loss"}, ...], "method",
    "topk_frac"}."""
    parts = data["parts"]
    mean_delta = jax.tree.map(
        lambda *ds: np.mean(np.stack([np.asarray(d, np.float32)
                                      for d in ds]), axis=0),
        *[p["delta"] for p in parts])
    msgs, _ = compress_tree(mean_delta, data.get("method", "int8"),
                            float(data.get("topk_frac", 0.1)))
    raw = sum(np.asarray(l).nbytes for l in jax.tree.leaves(mean_delta))
    return {"msgs": msgs,
            "mean_loss": float(np.mean([p["loss"] for p in parts])),
            "raw_bytes": raw}


# ---------------------------------------------------------------------------
# FedAvg coordinator over the FaaS layer
# ---------------------------------------------------------------------------

class FedAvgCoordinator:
    """Aggregates compressed deltas from N funcX endpoints.

    ``local_train_fn`` must be a registered function id whose payload is
    {"params": pytree, "seed": int, "steps": int} and which returns
    {"delta": pytree, "loss": float} — see tests/examples for the canonical
    implementation. Each endpoint keeps its own error-feedback state."""

    def __init__(self, client, local_train_fn: str,
                 endpoint_ids: List[str], *, method: str = "int8",
                 topk_frac: float = 0.1):
        self.client = client
        self.fn = local_train_fn
        self.endpoints = endpoint_ids
        self.method = method
        self.topk_frac = topk_frac
        self._err: Dict[str, Any] = {}
        self.bytes_sent = 0
        self.bytes_uncompressed = 0

    def round(self, params: Any, *, local_steps: int = 5,
              seed: int = 0) -> Tuple[Any, Dict[str, float]]:
        host_params = jax.tree.map(lambda a: np.asarray(a), params)
        # fan out local training through the FaaS layer
        tids = [self.client.run(self.fn, eid,
                                data={"params": host_params,
                                      "seed": seed * 1000 + i,
                                      "steps": local_steps})
                for i, eid in enumerate(self.endpoints)]
        results = [self.client.get_result(t, timeout=600) for t in tids]

        # endpoint-side compression (error feedback per endpoint)
        deltas, losses = [], []
        for eid, res in zip(self.endpoints, results):
            msgs, new_err = compress_tree(
                res["delta"], self.method, self.topk_frac,
                self._err.get(eid))
            self._err[eid] = new_err
            self.bytes_sent += compressed_bytes(msgs)
            self.bytes_uncompressed += sum(
                np.asarray(l).nbytes for l in jax.tree.leaves(res["delta"]))
            deltas.append(decompress_tree(msgs))
            losses.append(float(res["loss"]))

        # FedAvg: mean of deltas applied to the global params
        n = len(deltas)
        mean_delta = jax.tree.map(
            lambda *ds: np.mean(np.stack(ds), axis=0), *deltas)
        new_params = jax.tree.map(
            lambda p, d: (np.asarray(p) + d).astype(np.asarray(p).dtype),
            host_params, mean_delta)
        metrics = {
            "mean_loss": float(np.mean(losses)),
            "compression_ratio": (self.bytes_uncompressed
                                  / max(self.bytes_sent, 1)),
        }
        return jax.tree.map(jnp.asarray, new_params), metrics

    def round_refs(self, params: Any, *, arch: str, executor,
                   aggregate_fn: str, local_steps: int = 5, seed: int = 0,
                   seq: int = 8, batch: int = 8,
                   aggregate_endpoint: Optional[str] = None,
                   timeout: float = 600.0):
        """One FedAvg round where the heavy deltas never touch the
        coordinator (DESIGN.md §9+§10 together).

        Local training fans out through the futures-native ``executor``
        with ``warmth_key=train_warmth_key(...)`` so repeat rounds land on
        the worker already holding the jitted step. With the endpoints'
        ``stage_limit`` set below the raw delta size, each result comes
        back as a cross-endpoint **DataRef**; the aggregation task is then
        submitted to one endpoint with those refs in its payload — stage-in
        fetches the deltas peer-direct, and only the compressed mean rides
        the hub back. Returns ``(new_params, metrics, parts)`` where
        ``parts`` are the raw per-endpoint results (DataRefs, for callers
        that want to assert the transport shape).

        Compression happens once, on the aggregated mean, so there is no
        per-endpoint error-feedback state on this path."""
        host_params = jax.tree.map(lambda a: np.asarray(a), params)
        wk = train_warmth_key(arch, seq)
        futs = [executor.submit(
                    self.fn,
                    {"arch": arch, "params": host_params,
                     "seed": seed * 1000 + i, "steps": local_steps,
                     "seq": seq, "batch": batch},
                    endpoint_id=eid, warmth_key=wk)
                for i, eid in enumerate(self.endpoints)]
        parts = [f.result(timeout=timeout) for f in futs]

        agg = executor.submit(
            aggregate_fn,
            {"parts": parts, "method": self.method,
             "topk_frac": self.topk_frac},
            endpoint_id=aggregate_endpoint or self.endpoints[0],
        ).result(timeout=timeout)

        mean_delta = decompress_tree(agg["msgs"])
        self.bytes_sent += compressed_bytes(agg["msgs"])
        self.bytes_uncompressed += int(agg["raw_bytes"])
        new_params = jax.tree.map(
            lambda p, d: (np.asarray(p) + d).astype(np.asarray(p).dtype),
            host_params, mean_delta)
        metrics = {
            "mean_loss": float(agg["mean_loss"]),
            "compression_ratio": (self.bytes_uncompressed
                                  / max(self.bytes_sent, 1)),
        }
        return jax.tree.map(jnp.asarray, new_params), metrics, parts
