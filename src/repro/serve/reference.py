"""Plain reference for the served generation path.

The fabric prefills once and then decodes through the KV cache. The
reference rebuilds the same weights from the fabric's seed and, for every
served token, runs a teacher-forced full prefill over the prompt plus the
tokens served before it — no cache, no decode step, no fabric
environment. Both sides compute in the config's activation dtype.
"""
from __future__ import annotations

import numpy as np

# Largest gap (reference max logit − served token's reference logit) that
# still counts as agreement: bf16 near-ties may resolve either way.
LOGIT_TOLERANCE = 0.1


class TeacherForcedReference:
    """One arch's reference weights and its jitted no-cache prefill,
    built once and reused for every checked request."""

    def __init__(self, arch: str):
        import jax

        from ..configs import get_config
        from ..models import get_model
        from .fabric import PARAMS_SEED

        model = get_model(get_config(arch))
        self.params = model.init(jax.random.PRNGKey(PARAMS_SEED))
        self._last_logits = jax.jit(
            lambda p, t: model.prefill(p, {"tokens": t})[0])

    def gaps(self, prompt: np.ndarray, served: np.ndarray) -> np.ndarray:
        """``(n,)`` gaps for one ``(1, S)`` prompt and its ``(1, n)``
        served tokens: reference max logit minus the served token's
        reference logit at each position. A gap of 0 means the served
        token is the reference argmax; agreement is every gap ≤
        :data:`LOGIT_TOLERANCE`."""
        seq = np.concatenate([prompt, served], axis=1).astype(np.int32)
        S = prompt.shape[1]
        gaps = []
        for i in range(served.shape[1]):
            logits = np.asarray(self._last_logits(self.params,
                                                  seq[:, :S + i]),
                                np.float32)[0]
            gaps.append(float(logits.max() - logits[served[0, i]]))
        return np.asarray(gaps)
