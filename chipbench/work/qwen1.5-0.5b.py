"""Work of one qwen1.5-0.5b step, from the published sizes.

The operations and the least bytes the algorithm needs, not what one
implementation does: weights are read once at bfloat16 (2 bytes) however
the program stores or casts them; attention counts only the causal pairs;
the head runs on the positions whose logits are returned (the last one in
a prefill). ``cfg`` is the configuration file's dict.
"""
BF16 = 2
F32 = 4


def _layer_params(cfg):
    d, H, KVH = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd = d // H
    attn = d * H * hd + 2 * d * KVH * hd + H * hd * d
    return attn + 3 * d * cfg["intermediate_size"]


def _kv_row(cfg):
    """Elements of K and V one position adds to the cache, all layers."""
    d, H, KVH = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    return 2 * cfg["num_hidden_layers"] * KVH * (d // H)


def prefill(cfg, prompt_len):
    """``(flops, bytes)`` of one prefill of ``prompt_len`` tokens."""
    S, L, d, V = (prompt_len, cfg["num_hidden_layers"], cfg["hidden_size"],
                  cfg["vocab_size"])
    pairs = S * (S + 1) // 2
    flops = (2 * S * L * _layer_params(cfg) + 2 * 2 * L * d * pairs
             + 2 * d * V)
    nbytes = (BF16 * (L * _layer_params(cfg) + d * V + S * d)
              + BF16 * S * _kv_row(cfg) + F32 * V)
    return flops, nbytes


def decode(cfg, context):
    """``(flops, bytes)`` of one decode step over ``context`` cached
    positions (the new one included)."""
    L, d, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    flops = 2 * L * _layer_params(cfg) + 2 * 2 * L * d * context + 2 * d * V
    nbytes = (BF16 * (L * _layer_params(cfg) + d * V + d)
              + BF16 * context * _kv_row(cfg) + F32 * V)
    return flops, nbytes
