"""Work of one step of one chip's share of DeepSeek-V2-Lite, from the
configuration file's sizes.

The operations and the least bytes the algorithm needs, not what one
implementation does: weights are read once at bfloat16 (2 bytes); the
routed experts count at their expected share under uniform routing over
the router's ``router_experts``, of which this chip holds
``n_routed_experts``. A token takes ``k = num_experts_per_tok`` distinct
experts, so it computes ``k * held / router`` held expert slots, and a
step over ``S`` tokens touches ``held * (1 - (1 - k / router) ** S)`` of
the held experts of a layer. Prefill attends over expanded keys and
values (causal pairs only); decode attends against the latent cache
(absorbed), which it reads whole. The head runs over the held vocabulary
slice, at the positions whose logits are returned (the last one in a
prefill). ``cfg`` is the configuration file's dict.
"""
BF16 = 2
F32 = 4


def _sizes(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return d, H, nope, rope, vd, cfg["kv_lora_rank"]


def _attn_params(cfg):
    d, H, nope, rope, vd, r = _sizes(cfg)
    return (d * H * (nope + rope) + d * (r + rope) + r * H * nope
            + r * H * vd + H * vd * d)


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg):
    """``(dense layers, expert layers)``."""
    k = cfg["first_k_dense_replace"]
    return k, cfg["num_hidden_layers"] - k


def _fixed_params(cfg):
    """Weights every token reads: attention in every layer, the dense
    layers' FFN, each expert layer's router and shared experts."""
    d = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    router = d * cfg["deployment"]["router_experts"]
    shared = cfg["n_shared_experts"] * _expert_params(cfg)
    return (cfg["num_hidden_layers"] * _attn_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + moe * (router + shared))


def _held_slots(cfg):
    """Held expert slots one token computes, on average."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["deployment"]["router_experts"])


def _touched(cfg, tokens):
    """Held experts of one layer that ``tokens`` tokens touch, on
    average."""
    share = cfg["num_experts_per_tok"] / cfg["deployment"]["router_experts"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - share) ** tokens)


def _latent_row(cfg):
    """Elements of the latent cache one position adds, all layers."""
    _d, _H, _n, rope, _v, r = _sizes(cfg)
    return cfg["num_hidden_layers"] * (r + rope)


def prefill(cfg, prompt_len):
    """``(flops, bytes)`` of one prefill of ``prompt_len`` tokens."""
    S, d, V = prompt_len, cfg["hidden_size"], cfg["vocab_size"]
    _d, H, nope, rope, vd, _r = _sizes(cfg)
    _dense, moe = _layers(cfg)
    L = cfg["num_hidden_layers"]
    pairs = S * (S + 1) // 2
    flops = (2 * S * _fixed_params(cfg)
             + 2 * S * moe * _held_slots(cfg) * _expert_params(cfg)
             + 2 * L * H * (nope + rope + vd) * pairs
             + 2 * d * V)
    nbytes = (BF16 * (_fixed_params(cfg)
                      + moe * _touched(cfg, S) * _expert_params(cfg)
                      + d * V + S * d)
              + BF16 * S * _latent_row(cfg) + F32 * V)
    return flops, nbytes


def decode(cfg, context):
    """``(flops, bytes)`` of one decode step over ``context`` cached
    positions (the new one included)."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    _d, H, _nope, rope, _vd, r = _sizes(cfg)
    _dense, moe = _layers(cfg)
    L = cfg["num_hidden_layers"]
    flops = (2 * _fixed_params(cfg)
             + 2 * moe * _held_slots(cfg) * _expert_params(cfg)
             + 2 * L * H * (r + rope + r) * context
             + 2 * d * V)
    nbytes = (BF16 * (_fixed_params(cfg)
                      + moe * _touched(cfg, 1) * _expert_params(cfg)
                      + d * V + d)
              + BF16 * context * _latent_row(cfg) + F32 * V)
    return flops, nbytes
