"""Runtime knobs orthogonal to the architecture config: the attention block
sizes and the rematerialisation policy. Part of the *compile signature*
(funcX container type) together with the ModelConfig, ShapeConfig, and mesh."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class RunKnobs:
    q_block: int = 1024
    kv_block: int = 1024
    remat: str = "full"          # "none" | "dots" | "full"


DEFAULT_KNOBS = RunKnobs()
