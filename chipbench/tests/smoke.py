"""Toy-size stand-ins of the cells' configurations and mixes, for runs of
the harness on the CPU. The sizes are the program's ``@smoke`` presets."""

QWEN = {
    "arch": "qwen1.5-0.5b@smoke", "family": "dense",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 128,
    "padded_vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "qkv_bias": True,
    "endpoint": {"managers": 1, "workers": 2, "transport": "tcp",
                 "shm": False, "manager_timeout_s": 120.0},
    "set_in_preset": {"rope_theta": 1000000.0},
}
GEN = {"function": "generate", "prompt_len": 32,
       "output_len": {"dist": "uniform", "min": 2, "max": 6}, "greedy": True,
       "loop": "open", "arrivals": "poisson", "rate_per_s": 6.0}
PREFILL = {"function": "prefill", "prompt_len": 16, "greedy": True,
           "loop": "closed", "concurrency": 8}

# The limit for the toy size, between what the program reads there on the
# CPU (widest gap up to 0.002 over 5 seeds) and what the float8 control
# reads (at least 0.042).
LIMIT = 0.02
