"""DeepSeek-V2-Lite against its plain reference, at the ``@smoke`` size on
the CPU with seeded random weights: prefill and cached decode, one chip's
share of the expert layer, dropless routing, and YaRN's closed form.

The reference (``chipbench/reference/deepseek_v2.py``) imports nothing of
the program; it rebuilds the same weights from the seed by the published
path rule and computes in float32 at ``highest`` precision."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.models.attention import mla_rope_freqs, mla_softmax_scale
from repro.models.knobs import RunKnobs
from repro.models.moe import moe_block, moe_spec
from repro.models.params import init_params
from repro.serve.serve_step import make_decode, make_prefill
from repro.sharding.rules import ShardCtx

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench.reference import deepseek_v2 as ref, matmul, weights  # noqa: E402

SMOKE = "deepseek-v2-lite@smoke"
KNOBS = RunKnobs(q_block=8, kv_block=8)
SEED = 7


def reference_config(cfg):
    """The reference's HF-named configuration of a program config."""
    m, mo, y = cfg.mla, cfg.moe, cfg.mla.yarn
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "kv_lora_rank": m.kv_lora_rank, "q_lora_rank": m.q_lora_rank,
        "first_k_dense_replace": mo.first_k_dense,
        "n_routed_experts": mo.n_held,
        "moe_intermediate_size": mo.d_ff_expert,
        "n_shared_experts": mo.n_shared_experts,
        "intermediate_size": cfg.d_ff, "num_experts_per_tok": mo.top_k,
        "norm_topk_prob": mo.norm_topk_prob,
        "routed_scaling_factor": mo.routed_scaling_factor,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {
            "type": "yarn", "factor": y.factor,
            "original_max_position_embeddings":
                y.original_max_position_embeddings,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
        "vocab_size": cfg.vocab_size, "padded_vocab_size": cfg.padded_vocab(),
        "deployment": {"router_experts": mo.n_experts,
                       "first_held_expert": mo.expert_first},
    }


def served_logits(cfg, params, toks, prompt):
    """Prefill ``prompt`` tokens, then decode the rest through the cache;
    the logits of every step, ``(B, S - prompt + 1, vocab)``."""
    model = get_model(cfg)
    prefill = jax.jit(make_prefill(model, knobs=KNOBS,
                                   cache_len=toks.shape[1]))
    decode = jax.jit(make_decode(model, knobs=KNOBS))
    logits, cache = prefill(params, {"tokens": toks[:, :prompt]})
    out = [logits]
    for i in range(prompt, toks.shape[1]):
        logits, cache = decode(params, cache, {"tokens": toks[:, i:i + 1]})
        out.append(logits)
    return np.asarray(jnp.stack(out, 1), np.float32)[..., :cfg.vocab_size]


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config(SMOKE)
    params = get_model(cfg).init(jax.random.PRNGKey(SEED))
    B, S, prompt = 2, 20, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size, jnp.int32)
    rc = reference_config(cfg)
    want = np.asarray(ref.logits(rc, weights.make(ref.specs(rc), SEED),
                                 toks, prompt - 1, S - prompt + 1,
                                 matmul.full))
    return cfg, params, toks, prompt, want


def test_reference_rebuilds_the_served_weights(smoke):
    cfg, params, *_ = smoke
    rc = reference_config(cfg)
    built = weights.make(ref.specs(rc), SEED)
    served = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(built) == set(served)
    for path, w in built.items():
        # one eager and one jitted draw of the same normal: equal to float32
        # rounding
        np.testing.assert_allclose(np.asarray(served[path]), np.asarray(w),
                                   rtol=0, atol=1e-6, err_msg=path)


def test_prefill_and_cached_decode_match_the_reference_in_float32(smoke):
    """The program computed in float32: prefill's logits and each cached
    decode step's equal the reference's full forward pass to float32
    rounding (observed 5e-6). Any missing or altered term (YaRN, the
    interleaved rope, the softmax scale, a routed or shared expert) moves
    them by 1e-2 or more, and computing in bfloat16 by ~1e-1."""
    cfg, params, toks, prompt, want = smoke
    got = served_logits(cfg.with_(dtype="float32"), params, toks, prompt)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


def test_prefill_and_cached_decode_match_the_reference_in_bfloat16(smoke):
    """The configuration's own precision: bfloat16 activations and weights
    round to 8 bits of mantissa, so logits of magnitude ~3 land within a
    few hundredths (observed 0.09 at most); the float8 control lands
    ~0.9 away."""
    cfg, params, toks, prompt, want = smoke
    got = served_logits(cfg, params, toks, prompt)
    np.testing.assert_allclose(got, want, rtol=0, atol=0.25)
    assert np.abs(got - want).mean() < 0.03


def _moe_params(cfg, key):
    return init_params(moe_spec(cfg), key)


def _layer_by_hand(x, p, cfg):
    """The uncut expert layer in float64: softmax router over all experts,
    greedy top-k, the weights as configured, every expert's SwiGLU on
    every token it was routed to, plus the shared experts."""
    m = cfg.moe
    x = np.asarray(x, np.float64)
    f = lambda a: np.asarray(a, np.float64)
    logits = x @ f(p["router"])
    gates = np.exp(logits - logits.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    top = np.argsort(-gates, -1, kind="stable")[:, :m.top_k]
    w = np.take_along_axis(gates, top, -1)
    if m.norm_topk_prob:
        w /= w.sum(-1, keepdims=True)
    w *= m.routed_scaling_factor
    silu = lambda a: a / (1 + np.exp(-a))
    swiglu = lambda h, g, u, d: (silu(h @ f(g)) * (h @ f(u))) @ f(d)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, wt in zip(top[t], w[t]):
            y[t] += wt * swiglu(x[t], p["w_gate"][e], p["w_up"][e],
                                p["w_down"][e])
    if "shared" in p:
        sh = p["shared"]
        y += swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    return y


def _share(cfg, p, first, held):
    """One chip's share: ``held`` experts from ``first``, the rest of the
    layer (router, shared experts) replicated."""
    moe = dataclasses.replace(cfg.moe, experts_held=held, expert_first=first)
    q = dict(p, **{k: p[k][first:first + held]
                   for k in ("w_gate", "w_up", "w_down")})
    return cfg.with_(moe=moe), q


def test_the_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """Four chips of two experts each: their partial layers, with the
    shared experts every chip computes counted once, add up to the uncut
    layer. float32 throughout; the sum differs from the float64 layer by
    rounding alone."""
    cfg = get_config(SMOKE).with_(dtype="float32")
    p = _moe_params(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, cfg.d_model))
    n = cfg.moe.n_experts // 4
    shares = [moe_block(x, q, c, ShardCtx())[0]
              for c, q in (_share(cfg, p, j * n, n) for j in range(4))]
    sh = p["shared"]
    shared = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"]) @ sh["w_down"]
    total = sum(shares) - 3 * shared
    want = _layer_by_hand(x[0], jax.tree.map(np.asarray, p), cfg)
    np.testing.assert_allclose(np.asarray(total[0]), want, rtol=0, atol=1e-4)
    whole = moe_block(x, p, cfg, ShardCtx())[0]
    np.testing.assert_allclose(np.asarray(whole[0]), want, rtol=0, atol=1e-4)
    # each share alone is only part of it
    assert all(np.abs(np.asarray(s[0] - shared[0])).max() > 1e-2
               for s in shares)


def test_a_routing_forced_onto_one_expert_drops_no_token():
    """Every token's first choice is expert 0: dropless serving gives each
    its full layer; capacity buffers (training's choice) could take only
    ``1.25 * T * k / E`` of them."""
    cfg = get_config(SMOKE).with_(dtype="float32")
    p = _moe_params(cfg, jax.random.PRNGKey(4))
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    T = 64
    x = jax.random.normal(jax.random.PRNGKey(5), (1, T, cfg.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)   # router column 0 wins
    want = _layer_by_hand(x[0], jax.tree.map(np.asarray, p), cfg)
    gates = jax.nn.softmax(x[0] @ p["router"])
    assert (jnp.argmax(gates, -1) == 0).all()
    y = moe_block(x, p, cfg, ShardCtx())[0]
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=0, atol=1e-4)
    dropped = moe_block(x, p, cfg, ShardCtx(), dropless=False)[0]
    lost = np.abs(np.asarray(dropped[0]) - want).max(-1) > 1e-3
    assert lost.sum() >= T - int(1.25 * T * cfg.moe.top_k / 8) - 1


def test_the_training_aux_loss_averages_the_expert_layers_alone():
    """A zero router gives every token uniform gates and expert 0 first,
    so each expert layer's Switch loss ``E * sum f_e p_e`` is exactly 1;
    the leading dense layer has none and must not dilute the mean."""
    cfg = get_config(SMOKE).with_(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    moe = params["blocks"]["moe"]
    moe["router"] = jnp.zeros_like(moe["router"])
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size, jnp.int32)
    loss, metrics = model.loss(params, {"tokens": toks, "labels": toks})
    assert np.isfinite(float(loss))
    assert float(metrics["moe_aux"]) == pytest.approx(1.0, abs=1e-6)


def test_yarn_frequencies_and_softmax_scale_match_their_closed_form():
    """DeepSeek-V2-Lite: rope dim 64, base 1e4, factor 40 over 4,096
    positions, beta_fast 32 and beta_slow 1. The correction dims are
    64·ln(4096/(2π·β))/(2·ln 1e4): 10.47 → floor 10 and 22.51 → ceil 23.
    Below 10 the frequencies are the plain ones, above 23 divided by 40,
    linear in between. mscale = mscale_all_dim, so cos/sin are unscaled,
    and the softmax scale is 192^-0.5 · (0.1·0.707·ln 40 + 1)²."""
    cfg = get_config("deepseek-v2-lite@ep8")
    i = np.arange(32)
    plain = 1.0 / 10_000.0 ** (2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    want = plain / 40 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(mla_rope_freqs(cfg), want, rtol=1e-6)
    assert mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)
    assert mla_softmax_scale(get_config("minicpm3-4b")) == 96 ** -0.5


def test_the_ep8_share_is_one_chip_of_the_published_model():
    published, share = (get_config("deepseek-v2-lite"),
                        get_config("deepseek-v2-lite@ep8"))
    assert share.with_(name=published.name, vocab_size=102_400,
                       moe=published.moe) == published
    assert (share.moe.n_experts, share.moe.n_held, share.moe.top_k) == (
        64, 8, 6)
    assert share.vocab_size == 12_800 == share.padded_vocab()
    spec = get_model(share).spec()
    assert spec["blocks"]["moe"]["router"].shape == (26, 2048, 64)
    assert spec["blocks"]["moe"]["w_gate"].shape == (26, 8, 2048, 1408)
    assert spec["dense_blocks"]["ffn"]["w_gate"].shape == (1, 2048, 10_944)
    assert get_model(share).param_count() == 2_743_987_712
