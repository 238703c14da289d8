"""Serving fabric (DESIGN.md §10): the jax_pallas model zoo behind funcX.

Every ``(arch, step, shape-bucket)`` combination is one **warmth key** —
``jit/<arch>/<step>/b<bucket>`` — used as the task's container type.
``<arch>`` names the model at its published width (``qwen1.5-0.5b``);
``<arch>@smoke`` names its reduced toy-size config, which CPU runs use.
Workers build the jit-compiled executables as the container environment,
with the weights resident in the compute dtype the steps read them in
(``Model.compute_params``: no fp32 master is kept, and no step casts a
weight), so the first request per key pays the real
``jax.jit`` compile (the cold start the paper measures for containers)
and the WarmCache advertises the key through the ordinary warm dicts.
Routing — federation and manager tier alike — then steers requests for a
model/shape toward endpoints and workers already holding that compiled
executable, exactly as it steers toward warm containers.

The zoo's cross product is never enumerated: :func:`install` registers a
``jit/`` prefix **spec factory** on the ContainerRegistry, minting each
concrete spec on first demand. Subprocess endpoints opt in via

    python -m repro.core.endpoint ... --containers repro.serve.fabric:install
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import get_config
from ..core.spans import device_wait, span, stamp
from ..core.tasks import EXPERTS_READ, FIRST_TOKEN, TOKENS
from ..core.warming import ContainerRegistry, ContainerSpec

JIT_PREFIX = "jit/"
STEP_KINDS = ("generate", "prefill", "decode")
_MIN_BUCKET = 16
_DECODE_HORIZON = 32           # cache headroom compiled past the prompt
# prefill's attention block: a 2,048-token bucket takes 4 x 4 blocks a
# layer, where blocks of 64 took 1,024 steps of tiny products
_ATTN_BLOCK = 512


# ---------------------------------------------------------------------------
# warmth keys
# ---------------------------------------------------------------------------

def shape_bucket(prompt_len: int) -> int:
    """Pad bucket for a prompt length: the next power of two (≥ 16), so a
    handful of compiled shapes serves arbitrary prompts."""
    b = _MIN_BUCKET
    while b < prompt_len:
        b *= 2
    return b


def jit_key(arch: str, step: str = "generate",
            bucket: int = _MIN_BUCKET) -> str:
    """The warmth key naming one compiled executable."""
    if step not in STEP_KINDS:
        raise ValueError(f"unknown step kind {step!r} (one of {STEP_KINDS})")
    return f"{JIT_PREFIX}{arch}/{step}/b{int(bucket)}"


def parse_jit_key(key: str) -> Tuple[str, str, int]:
    """``jit/<arch>/<step>/b<bucket>`` → ``(arch, step, bucket)``."""
    if not key.startswith(JIT_PREFIX):
        raise ValueError(f"not a jit warmth key: {key!r}")
    arch, step, bucket = key[len(JIT_PREFIX):].rsplit("/", 2)
    if step not in STEP_KINDS or not bucket.startswith("b"):
        raise ValueError(f"malformed jit warmth key: {key!r}")
    return arch, step, int(bucket[1:])


def pad_to_bucket(tokens: np.ndarray) -> np.ndarray:
    """Right-pad a ``(B, S)`` prompt with zeros to its shape bucket, so
    every request in a bucket hits the same compiled executable."""
    tokens = np.asarray(tokens)
    bucket = shape_bucket(tokens.shape[1])
    if tokens.shape[1] == bucket:
        return tokens
    pad = np.zeros((tokens.shape[0], bucket - tokens.shape[1]),
                   dtype=tokens.dtype)
    return np.concatenate([tokens, pad], axis=1)


# ---------------------------------------------------------------------------
# container build == jit compile (the real cold start)
# ---------------------------------------------------------------------------

PARAMS_SEED = 0                 # weights are random, made from this seed


def build_steps(model, bucket: int):
    """The jitted ``(prefill, decode)`` pair one environment serves a
    bucket with: prefill leaves ``_DECODE_HORIZON`` slots of cache past
    the prompt for decode to fill. Prefill attends in blocks of up to
    ``_ATTN_BLOCK`` positions (a bucket below it is one block)."""
    import jax

    from ..models.knobs import RunKnobs
    from .serve_step import make_decode, make_prefill

    knobs = RunKnobs(q_block=_ATTN_BLOCK, kv_block=_ATTN_BLOCK)
    return (jax.jit(make_prefill(model, knobs=knobs,
                                 cache_len=bucket + _DECODE_HORIZON)),
            jax.jit(make_decode(model, knobs=knobs)))


def _build_env(arch: str, step: str, bucket: int) -> Dict[str, Any]:
    """Build one serving environment: init params in the compute dtype,
    jit-compile the step executables **eagerly** at the
    bucket shape — the build time the WarmCache records is the actual
    compile cost. Records the device the params live on, which every
    served result reports."""
    import jax
    import jax.numpy as jnp

    from ..models import get_model

    enable_compile_cache()
    cfg = get_config(arch)
    model = get_model(cfg)
    # init and cast in one jitted call: no whole fp32 tree is ever live
    params = jax.jit(lambda key: model.compute_params(model.init(key)))(
        jax.random.PRNGKey(PARAMS_SEED))
    prefill, decode = build_steps(model, bucket)
    probe = jnp.zeros((1, bucket), jnp.int32)
    out = prefill(params, {"tokens": probe})
    if step != "prefill":                   # decode executable too
        out = decode(params, out[1], {"tokens": probe[:, :1]})
    jax.block_until_ready(out)
    device, = jax.tree.leaves(params)[0].devices()
    return {"arch": arch, "step": step, "bucket": bucket, "cfg": cfg,
            "model": model, "params": params, "prefill": prefill,
            "decode": decode, "uses": 0,
            "device": {"platform": device.platform,
                       "device_kind": device.device_kind}}


def _spec_for(container_type: str) -> ContainerSpec:
    arch, step, bucket = parse_jit_key(container_type)

    def build() -> Dict[str, Any]:
        return _build_env(arch, step, bucket)

    return ContainerSpec(container_type, build=build)


def install(registry: ContainerRegistry) -> ContainerRegistry:
    """Expose the whole model zoo on ``registry``: any ``jit/...`` type a
    task asks for is minted on first demand. The ``--containers`` hook
    for subprocess endpoints — and callable on a same-process registry."""
    registry.register_factory(JIT_PREFIX, _spec_for)
    return registry


# ---------------------------------------------------------------------------
# registered funcX functions (module-level: resolvable by reference from
# subprocess endpoints via plain pickle)
# ---------------------------------------------------------------------------

def _served_by(env) -> Dict[str, Any]:
    """What every served result reports beside its output: the model,
    the shape bucket and the device that ran it."""
    return {"arch": env["arch"], "bucket": env["bucket"], **env["device"]}


# Each served function runs as three kinds of leaf span (core/spans.py):
# ``fabric.put`` (the prompt to the device), ``fabric.dispatch`` (enqueue
# the jitted steps and the sampling) and ``fabric.fetch`` (one blocking
# device→host read, whose seconds are the task's device wait).

def _put(data):
    """The prompt, padded to its shape bucket, on the device."""
    import jax.numpy as jnp

    with span("fabric.put"):
        return jnp.asarray(pad_to_bucket(np.asarray(data["tokens"])),
                           jnp.int32)


def _fetch(x) -> np.ndarray:
    """One host round trip: wait for ``x`` and copy it to the host."""
    with device_wait("fabric.fetch"):
        return np.asarray(x)


def serve_generate(data, env):
    """Batched generation inside the warm jit environment. Reports
    ``warm`` from an env-held uses counter, so clients can measure the
    warm-hit rate without reaching into worker internals. Stamps the
    moment the first token is on the host and the tokens fetched; for an
    MoE model also the held experts its decode steps read, per expert
    layer and step, from the cache's counter, fetched once after the last
    token.

    Each decode step is enqueued before the host waits on the token
    before it, so the device never idles while the host dispatches."""
    import jax

    from ..models import transformer
    from .sampler import sample

    uses, env["uses"] = env["uses"], env["uses"] + 1
    tokens = _put(data)
    n_new = max(int(data.get("n_tokens", 4)), 1)
    with span("fabric.dispatch"):
        logits, cache = env["prefill"](env["params"], {"tokens": tokens})
        key = jax.random.PRNGKey(int(data.get("seed", 0)))
        tok = sample(logits, key, 0.0)
    outs = []
    for i in range(n_new):
        nxt = None
        if i + 1 < n_new:
            with span("fabric.dispatch"):
                key, sub = jax.random.split(key)
                logits, cache = env["decode"](env["params"], cache,
                                              {"tokens": tok[:, None]})
                nxt = sample(logits, sub, 0.0)
        outs.append(_fetch(tok))
        if i == 0:
            stamp(FIRST_TOKEN)
        tok = nxt
    stamp(TOKENS, float(len(outs)))
    cfg = env["cfg"]
    if n_new > 1 and transformer.EXPERTS_READ in cache:
        steps = (n_new - 1) * (cfg.n_layers - cfg.moe.first_k_dense)
        stamp(EXPERTS_READ,
              float(_fetch(cache[transformer.EXPERTS_READ])) / steps)
    return {"tokens": np.stack(outs, axis=1), "warm": uses > 0,
            **_served_by(env)}


def serve_prefill(data, env):
    """One prefill step: returns the greedy next token (the cache stays
    worker-resident — decoding continues via :func:`serve_generate`)."""
    import jax.numpy as jnp

    uses, env["uses"] = env["uses"], env["uses"] + 1
    tokens = _put(data)
    with span("fabric.dispatch"):
        logits, _cache = env["prefill"](env["params"], {"tokens": tokens})
        next_token = jnp.argmax(logits, axis=-1)
    return {"next_token": _fetch(next_token),
            "warm": uses > 0, **_served_by(env)}


def serve_decode(data, env):
    """One decode step after a prefill of the given prompt — exercises
    the decode executable alone."""
    import jax.numpy as jnp

    uses, env["uses"] = env["uses"], env["uses"] + 1
    tokens = _put(data)
    with span("fabric.dispatch"):
        logits, cache = env["prefill"](env["params"], {"tokens": tokens})
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        logits, _cache = env["decode"](env["params"], cache,
                                       {"tokens": tok})
        next_token = jnp.argmax(logits, axis=-1)
    return {"next_token": _fetch(next_token),
            "warm": uses > 0, **_served_by(env)}


_STEP_FNS = {"generate": serve_generate, "prefill": serve_prefill,
             "decode": serve_decode}


def register_zoo(client, archs, *, step: str = "generate"):
    """Register the serving function once per arch with the service and
    return ``{arch: (function_id, container_type_for_bucket16)}`` — the
    convenience map benches and examples drive the fabric through. A bare
    arch id serves the published width; ``<arch>@smoke`` the toy size.
    The per-request container type (= warmth key) still varies by shape
    bucket; pass ``container_type=jit_key(arch, step, shape_bucket(S))``
    at submit time for non-default prompts."""
    fn = _STEP_FNS[step]
    out = {}
    for arch in archs:
        ct = jit_key(arch, step, _MIN_BUCKET)
        fid = client.register_function(fn, name=f"{step}/{arch}",
                                       container_type=ct)
        out[arch] = (fid, ct)
    return out
