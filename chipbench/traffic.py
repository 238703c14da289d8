"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``chipbench/traffic/<name>.json``) fixes the served function, the
prompt length, the output-length distribution and the loop:

- ``"loop": "open"`` with ``"arrivals": "poisson"`` and ``"rate_per_s"``:
  requests are due on a schedule whatever the system does;
- ``"loop": "closed"`` with ``"concurrency"``: that many requests are kept
  outstanding, a new one sent as each completes.

The arrival times and output lengths are drawn once, from a fixed stream,
for the window's length: every seed offers the same schedule, bursts
included, and the seed draws only the prompt ids (and, in the endpoint,
the weights). Near the knee a queue's tail depends on where the bursts and
the long requests fall, so a schedule that moved with the seed would make
the seed, not the system, the larger part of the spread.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
_SHAPE_STREAM = 20_221_012          # fixed stream for gaps and lengths
_CLOSED_POOL = 4096                 # distinct prompts a closed loop cycles


@dataclass
class Request:
    index: int
    due_s: Optional[float]          # offset from window open; None: closed
    prompt: np.ndarray              # (1, prompt_len) int32
    n_tokens: int                   # tokens to generate (1 for prefill)


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _output_lengths(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    spec = mix.get("output_len")
    if spec is None:
        return np.ones(n, np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    raise ValueError(f"unknown output_len dist {spec['dist']!r}")


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The requests of one run. Open loop: every request due in
    ``[0, seconds)``. Closed loop: a pool the loop cycles through."""
    shape_rng = np.random.default_rng(_SHAPE_STREAM)
    token_rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        if mix["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        rate = float(mix["rate_per_s"])
        gaps = shape_rng.exponential(1.0 / rate, int(rate * seconds * 2) + 64)
        n = int(np.searchsorted(np.cumsum(gaps), seconds))
        due = np.cumsum(gaps[:n])
    elif mix["loop"] == "closed":
        n, due = _CLOSED_POOL, None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    lengths = _output_lengths(mix, n, shape_rng)
    prompts = token_rng.integers(1, vocab, (n, 1, int(mix["prompt_len"])),
                                 dtype=np.int64).astype(np.int32)
    return [Request(i, None if due is None else float(due[i]), prompts[i],
                    int(lengths[i])) for i in range(n)]
