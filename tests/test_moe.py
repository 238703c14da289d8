"""The expert layer's two dropless routes, and the count of experts read.

A product of at most ``LOOP_MAX_PAIRS`` (token, expert) pairs loops over
the held experts that got a pair; above it the pairs go through
``lax.ragged_dot``. Both must give the same layer. The decode step counts
the held experts it read in the cache, and ``serve_generate`` stamps that
count per expert layer and step, fetched once after the last token. All
on the CPU, at toy widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import spans
from repro.core.tasks import EXPERTS_READ, Task
from repro.models import attention as attn
from repro.models import get_model, moe
from repro.models.common import embed_tokens, rms_norm, swiglu
from repro.models.knobs import RunKnobs
from repro.models.params import init_params
from repro.models.transformer import build_positions, layer_stacks
from repro.serve import fabric
from repro.serve.serve_step import make_decode, make_prefill
from repro.sharding.rules import ShardCtx

SMOKE = "deepseek-v2-lite@smoke"
T = 32                                  # tokens: T * top_k == LOOP_MAX_PAIRS


def _share(cfg, n_experts, top_k, held, first):
    return cfg.with_(moe=dataclasses.replace(
        cfg.moe, n_experts=n_experts, top_k=top_k, experts_held=held,
        expert_first=first))


# each token's experts, best first; experts 4-11 are held
ROUTINGS = {
    "no-held-expert": lambda t: [0, 1, 2, 3],
    "one-held-expert": lambda t: [0, 1, 2, 5] if t % 2 else [0, 1, 2, 3],
    "all-eight-held": lambda t: [4 + (t + j) % 8 for j in range(4)],
    "held-and-elsewhere": lambda t: [4 + t % 3, t % 4, 12 + t % 4, 7 + t % 2],
}
TOUCHED = {"no-held-expert": 0, "one-held-expert": 1, "all-eight-held": 8,
           "held-and-elsewhere": 5}


def _routed_layer(routing):
    """An expert layer of 16 experts, top-4, holding experts 4-11, and 32
    tokens routed as ``routing`` says: token ``t`` carries 4.0 in dimension
    ``t``, where the router's row gives its experts logits 8, 6, 4 and 2
    and every other expert 0; its remaining dimensions are noise the router
    does not see. Weights and tokens in bfloat16, as served."""
    cfg = _share(get_config(SMOKE), 16, 4, 8, 4)
    assert T * cfg.moe.top_k == moe.LOOP_MAX_PAIRS
    p = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(11))
    router = np.zeros((cfg.d_model, 16), np.float32)
    x = np.zeros((1, T, cfg.d_model), np.float32)
    x[0, :, T:] = np.random.default_rng(3).normal(
        size=(T, cfg.d_model - T))
    for t in range(T):
        x[0, t, t] = 4.0
        router[t, ROUTINGS[routing](t)] = [2.0, 1.5, 1.0, 0.5]
    p["router"] = jnp.asarray(router)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    return cfg, p, jnp.asarray(x, jnp.bfloat16)


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_expert_loop_equals_the_grouped_product(routing, monkeypatch):
    """At the rule's edge (32 tokens x top-4 = 128 pairs) the loop over
    touched experts reads only those and gives the grouped product's layer
    within bfloat16 rounding (the order in which a token's pairs add up in
    float32 may differ)."""
    cfg, p, x = _routed_layer(routing)
    y_loop, _, n_loop = moe.moe_block(x, p, cfg, ShardCtx())
    monkeypatch.setattr(moe, "LOOP_MAX_PAIRS", 0)
    y_ragged, _, n_ragged = moe.moe_block(x, p, cfg, ShardCtx())
    assert int(n_loop) == TOUCHED[routing]
    assert int(n_ragged) == cfg.moe.n_held
    y_loop, y_ragged = (np.asarray(y, np.float32) for y in (y_loop, y_ragged))
    scale = np.abs(y_ragged).max()
    np.testing.assert_allclose(y_loop, y_ragged, rtol=2 ** -7,
                               atol=2 ** -8 * scale)
    if routing == "no-held-expert":        # the shared experts alone
        sh = p["shared"]
        shared = swiglu(x[0], sh["w_gate"], sh["w_up"], sh["w_down"])
        np.testing.assert_array_equal(y_loop[0],
                                      np.asarray(shared, np.float32))


def _float32_share():
    """The smoke model in float32, as the share that holds experts 2-5 of
    its 8: a token's top-3 touches 0 to 3 of them."""
    cfg = get_config(SMOKE).with_(dtype="float32")
    return _share(cfg, 8, 3, 4, 2)


def test_decode_gives_the_same_logits_with_the_layer_scan_on_and_off():
    cfg = _float32_share()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0,
                              cfg.vocab_size, jnp.int32)
    out = {}
    for scan in (True, False):
        knobs = RunKnobs(q_block=8, kv_block=8, scan_layers=scan)
        prefill = jax.jit(make_prefill(model, knobs=knobs, cache_len=12))
        decode = jax.jit(make_decode(model, knobs=knobs))
        _, cache = prefill(params, {"tokens": toks[:, :8]})
        steps = []
        for i in range(8, 12):
            logits, cache = decode(params, cache,
                                   {"tokens": toks[:, i:i + 1]})
            steps.append(logits)
        out[scan] = (np.asarray(jnp.stack(steps)), int(cache[EXPERTS_READ]))
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=0,
                               atol=1e-5)
    assert out[True][1] == out[False][1] > 0


def _held_touched(cfg, params, seq, first):
    """Replay the forward pass of ``seq`` (1, S) layer by layer, and count,
    from each expert layer's router on the host, the held experts in the
    top-k of every position from ``first`` on."""
    m = cfg.moe
    held = set(range(m.expert_first, m.expert_first + m.n_held))
    knobs = RunKnobs(q_block=8, kv_block=8)
    x = embed_tokens(params["embed"]["tok"], seq, jnp.float32)
    positions = build_positions(cfg, *seq.shape)
    count = 0
    for key, n, is_moe in layer_stacks(cfg):
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params[key])
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            x = x + attn.attn_full(cfg, lp["attn"], h, positions, ShardCtx(),
                                   knobs)
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if not is_moe:
                f = lp["ffn"]
                x = x + swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
                continue
            logits = np.asarray(h[0], np.float64) @ np.asarray(
                lp["moe"]["router"], np.float64)
            top = np.argsort(-logits, -1, kind="stable")[:, :m.top_k]
            count += sum(len(held & set(top[s])) for s in
                         range(first, seq.shape[1]))
            x = x + moe.moe_block(h, lp["moe"], cfg, ShardCtx())[0]
    return count


def test_serve_generate_stamps_the_held_experts_its_decode_steps_read(
        monkeypatch):
    """The stamp is the count the router gives on the host, per expert
    layer and decode step; the counter crosses to the host once, after
    the last token."""
    cfg = _float32_share()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(8))
    bucket, n_new = 16, 6
    prefill, decode = fabric.build_steps(model, bucket)
    env = {"arch": cfg.name, "bucket": bucket, "cfg": cfg, "params": params,
           "prefill": prefill, "decode": decode, "uses": 0,
           "device": {"platform": "cpu", "device_kind": "cpu"}}
    fetched = []

    def fetch(x):
        fetched.append(np.shape(x))
        return np.asarray(x)

    monkeypatch.setattr(fabric, "_fetch", fetch)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (1, bucket), 0, cfg.vocab_size, jnp.int32))
    task = Task("f", "e", None, "c")
    with spans.bind("t", task.t):
        out = fabric.serve_generate({"tokens": prompt, "n_tokens": n_new},
                                    env)
    assert fetched == [(1,)] * n_new + [()]
    # decode step i took token i - 1 at position bucket + i - 1
    seq = jnp.asarray(np.concatenate(
        [prompt, out["tokens"][:, :n_new - 1]], 1), jnp.int32)
    moe_layers = cfg.n_layers - cfg.moe.first_k_dense
    want = _held_touched(cfg, params, seq, bucket) / (
        (n_new - 1) * moe_layers)
    assert 0 < want < cfg.moe.top_k
    assert task.t[EXPERTS_READ] == pytest.approx(want, abs=1e-12)
    assert task.latency_breakdown()[EXPERTS_READ] == task.t[EXPERTS_READ]
