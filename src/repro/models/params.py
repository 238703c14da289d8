"""Parameter-spec system.

Every model declares its parameters once, as a nested dict of :class:`ParamSpec`
(shape + logical axes + initializer). From that single declaration we derive:

- ``init_params``     — materialized arrays (seeded per path)
- ``logical_axes``    — same-structure pytree of logical-axis tuples, consumed
                        by ``repro.sharding.rules`` to build NamedShardings
- ``abstract_params`` — ShapeDtypeStructs for dry-run lowering (no allocation)
- ``count_params``    — exact parameter counts (used for roofline 6·N·D)

Stacked (scanned) layers are expressed by :func:`stack` which prepends a
``"layers"`` axis (never sharded).
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # "normal" | "zeros" | "ones" | "scaled_normal"
    scale: float = 0.02

    def stacked(self, n: int) -> "ParamSpec":
        return ParamSpec((n,) + self.shape, ("layers",) + self.axes, self.init, self.scale)


def stack(spec_tree: Any, n: int) -> Any:
    """Prepend a scan ('layers') dimension to every spec in the tree."""
    return jax.tree.map(lambda s: s.stacked(n), spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _init_leaf(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "const":
        return jnp.full(spec.shape, spec.scale, dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape, jnp.float32) * spec.scale).astype(dtype)
    if spec.init == "scaled_normal":
        # fan-in scaled (truncated-normal-free variant; keeps init fast)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(spec_tree: Any, key: jax.Array, dtype=jnp.float32) -> Any:
    """Materialize parameters; each leaf is seeded by folding in a stable
    hash of its path, so one key gives the same weights in every process."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=_is_spec)[0]
    treedef = jax.tree_util.tree_structure(spec_tree, is_leaf=_is_spec)
    arrays = []
    for path, spec in leaves_with_paths:
        path_str = jax.tree_util.keystr(path)
        leaf_key = jax.random.fold_in(key, zlib.crc32(path_str.encode()) % (2**31 - 1))
        arrays.append(_init_leaf(spec, leaf_key, dtype))
    return jax.tree_util.tree_unflatten(treedef, arrays)


def logical_axes(spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=_is_spec)


def abstract_params(spec_tree: Any, dtype=jnp.float32) -> Any:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
                        spec_tree, is_leaf=_is_spec)


def count_params(spec_tree: Any) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(spec_tree, is_leaf=_is_spec))


def scan_or_loop(body: Callable, carry: Any, xs: Any, *, scan: bool,
                 length: int):
    """``lax.scan(body, carry, xs)`` or an unrolled python loop with
    identical semantics (used by the roofline analysis lowerings — XLA's
    cost_analysis counts while bodies once, so unrolled variants give exact
    per-layer costs)."""
    if scan:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys:
        stacked = jax.tree.map(lambda *z: jnp.stack(z), *ys)
    else:
        stacked = None
    return carry, stacked


def cast_floats(tree: Any, dtype) -> Any:
    """Cast float leaves (mixed precision: bf16 compute / f32 master)."""
    def c(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dtype)
        return a
    return jax.tree.map(c, tree)


def tree_paths(spec_tree: Any) -> Dict[str, ParamSpec]:
    out = {}
    for path, spec in jax.tree_util.tree_flatten_with_path(spec_tree, is_leaf=_is_spec)[0]:
        out[jax.tree_util.keystr(path)] = spec
    return out
