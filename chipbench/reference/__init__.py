"""Plain references the comparison that decides ``correct`` runs.

They import nothing of the program under test. Each rebuilds the served
weights from the run's seed by the same published rule the program states
(one key per parameter, folded with the CRC-32 of the parameter's path)
and computes the forward pass in float32 at ``highest`` matmul precision,
over the whole sequence at once: no cache, no decode step, no chunking.
A family module (``<family>.py``, found by the configuration's
``family``) names its parameters and computes logits; :mod:`.weights`
makes the weights; :mod:`.matmul` holds the full-precision product and
the float8 one the control uses.
"""
import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent


def family(name: str):
    """The reference module for a configuration's ``family``."""
    if name in ("weights", "matmul") or not (HERE / f"{name}.py").is_file():
        raise ValueError(f"no reference for family {name!r}")
    return importlib.import_module(f"{__name__}.{name}")
