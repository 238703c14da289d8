"""Per-arch smoke tests (deliverable f): every assigned architecture, as a
REDUCED config of the same family, runs one forward/train step on CPU with
finite outputs and correct shapes. Full configs build only abstract
params (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (
    ARCH_IDS,
    SMOKE_SHAPES,
    TrainConfig,
    get_config,
    get_reduced_config,
)
from repro.models import concrete_batch, get_model, input_specs
from repro.train import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = get_reduced_config(arch)
    model = get_model(cfg)
    state = init_train_state(model, KEY)
    # warmup_steps=0 → full lr at step 0, so one step must move params
    step = jax.jit(make_train_step(model, TrainConfig(warmup_steps=0,
                                                      total_steps=4)))
    batch = concrete_batch(cfg, SMOKE_SHAPES["train_4k"], KEY)
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert int(new_state["step"]) == 1
    # params actually moved
    moved = any(
        not jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(new_state["params"])))
    assert moved


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_shapes(arch):
    cfg = get_reduced_config(arch)
    model = get_model(cfg)
    params = model.init(KEY)
    batch = concrete_batch(cfg, SMOKE_SHAPES["prefill_32k"], KEY,
                           kind="prefill")
    logits, cache = model.prefill(params, batch)
    B = batch["tokens"].shape[0]
    assert logits.shape == (B, cfg.padded_vocab())
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = model.decode_step(params, cache, {"tokens": tok})
    assert logits2.shape == (B, cfg.padded_vocab())
    assert jnp.isfinite(logits2).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_abstract_only(arch):
    """Full published configs must build abstract params without allocating."""
    import math
    cfg = get_config(arch)
    model = get_model(cfg)
    abs_params = model.abstract_params()
    n = sum(math.prod(l.shape) for l in jax.tree.leaves(abs_params))
    assert n == model.param_count()
    from repro.configs import SHAPES
    specs = input_specs(cfg, list(SHAPES.values())[0])
    assert all(hasattr(s, "shape") for s in specs.values())

