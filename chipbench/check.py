"""The comparison that decides ``correct``.

What the window served is held against the plain reference
(:mod:`chipbench.reference`), run once the endpoint has exited. For a
sample of the finished requests, drawn from the seed with the longest one
always in it, the reference computes the logits at every position where
the program produced a token, with the prompt and the tokens the program
served before it as input (teacher forcing). The number compared is the
widest gap by which a served token's reference logit lies below the
reference's best logit at that position: 0 where the program chose the
reference's argmax, small where a near-tie fell the other way under the
program's bfloat16, large where the program produced something else.

A served token outside the vocabulary (a padded row of the table, or no
row at all) has no reference logit: its gap is NaN, and :func:`widest`
reads any NaN as an infinite gap.

The control (:func:`compare` with ``control=True``) puts the reference,
computed with float8 products, in the program's place: at each position
it takes the token the float8 logits rank first and reads that token's gap
in the full-precision reference.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from . import reference
from .reference import matmul, weights

BATCH = 8                      # sequences per reference call
SAMPLE = {"generate": 16, "prefill": 256}   # requests compared per run


def sample(lengths: Sequence[int], seed: int, n: int) -> List[int]:
    """Indices of ``n`` finished requests drawn from the seed, the first
    of the longest among them."""
    lengths = np.asarray(lengths)
    if len(lengths) == 0:
        return []
    longest = int(np.argmax(lengths))
    rest = np.setdiff1d(np.arange(len(lengths)), [longest])
    rng = np.random.default_rng([int(seed), 0x5EED])
    pick = rng.choice(rest, min(n - 1, len(rest)), replace=False)
    return [longest] + sorted(int(i) for i in pick)


def served(window, mix: dict):
    """``(indices, tokens)`` of every finished request of a window: the
    generated tokens, or a prefill's next token."""
    finished = sorted(window.results)
    key = "tokens" if mix["function"] == "generate" else "next_token"
    return finished, [np.asarray(window.results[i][key]).reshape(-1)
                      for i in finished]


def window_sample(window, mix: dict, seed: int):
    """``(prompts, tokens, horizon)`` of the seed's sample of a window."""
    finished, tokens = served(window, mix)
    pick = sample([len(t) for t in tokens], seed, SAMPLE[mix["function"]])
    prompts = np.concatenate([window.requests[finished[j]].prompt
                              for j in pick])
    horizon = int(mix["output_len"]["max"]) if "output_len" in mix else 1
    return prompts, [tokens[j] for j in pick], horizon


def compare(cfg: dict, seed: int, prompts: np.ndarray,
            served: Sequence[np.ndarray], horizon: int, *,
            control: bool = False) -> dict:
    """Gaps for ``prompts`` ``(M, S)`` and the tokens served after each
    (``served[i]``, at most ``horizon`` of them). Returns
    ``{"gaps": [array per request]}``, and with ``control`` also
    ``"control"`` (the control's gaps) and ``"control_tokens"`` (the tokens
    the control ranks first)."""
    import jax
    import jax.numpy as jnp

    fam = reference.family(cfg["family"])
    params = weights.make(fam.specs(cfg), seed)
    M, S = prompts.shape
    T = int(horizon)
    seq = np.zeros((M, S + T - 1), np.int32)
    seq[:, :S] = prompts
    tok = np.zeros((M, T), np.int32)
    for i, s in enumerate(served):
        s = np.asarray(s, np.int32).reshape(-1)
        seq[i, S:S + len(s) - 1] = s[:-1]
        tok[i, :len(s)] = s

    def gaps(p, t, served_tok):
        ref = fam.logits(cfg, p, t, S - 1, T, matmul.full)
        best = ref.max(-1)
        out = {"gaps": best - jnp.take_along_axis(
            ref, served_tok[..., None], -1)[..., 0]}
        if control:
            low = fam.logits(cfg, p, t, S - 1, T, matmul.fp8)
            pick = jnp.argmax(low, -1)
            out["control"] = best - jnp.take_along_axis(
                ref, pick[..., None], -1)[..., 0]
            out["control_tokens"] = pick
        return out

    run = jax.jit(gaps)
    pad = (-M) % BATCH
    seq = np.concatenate([seq, np.zeros((pad,) + seq.shape[1:], np.int32)])
    tok = np.concatenate([tok, np.zeros((pad, T), np.int32)])
    parts = [jax.device_get(run(params, seq[i:i + BATCH], tok[i:i + BATCH]))
             for i in range(0, M + pad, BATCH)]
    out = {}
    for key in parts[0]:
        rows = np.concatenate([np.asarray(p[key]) for p in parts])
        out[key] = [rows[i, :len(np.asarray(served[i]).reshape(-1))]
                    for i in range(M)]
    return out


def widest(gaps: Sequence[np.ndarray]) -> float:
    """The widest gap of all requests; ``inf`` where any gap is NaN (a
    served token that no reference row scores)."""
    gaps = [np.asarray(g, np.float64) for g in gaps if len(g)]
    if not gaps:
        return 0.0
    if any(np.isnan(g).any() for g in gaps):
        return float("inf")
    return float(max(g.max() for g in gaps))


def outside_vocab(served: Sequence[np.ndarray], vocab: int) -> int:
    """How many served tokens lie outside ``[0, vocab)``."""
    return int(sum(((np.asarray(s) < 0) | (np.asarray(s) >= vocab)).sum()
                   for s in served))
