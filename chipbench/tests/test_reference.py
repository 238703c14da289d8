"""The plain reference against the program at its toy size, on the CPU,
and the control: the reference in float8 must fail the comparison where
the program passes it."""
import numpy as np
import pytest

from chipbench import check, reference
from chipbench.reference import matmul, weights
from chipbench.tests import smoke

CONFIGS = {"dense": smoke.QWEN}
SEED = 2 ** 32 + 77


def _program(cfg):
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    model = get_model(get_config(cfg["arch"]).with_(
        **cfg.get("set_in_preset", {})))
    return model, model.init(jax.random.PRNGKey(weights.weight_seed(SEED)))


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_reference_weights_are_the_served_weights(family):
    import jax

    cfg = CONFIGS[family]
    _, served = _program(cfg)
    flat = {jax.tree_util.keystr(p): np.asarray(v) for p, v in
            jax.tree_util.tree_flatten_with_path(served)[0]}
    mine = weights.make(reference.family(family).specs(cfg), SEED)
    assert sorted(flat) == sorted(mine)
    for path, v in flat.items():
        np.testing.assert_allclose(np.asarray(mine[path]), v, rtol=1e-6,
                                   atol=1e-7, err_msg=path)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_reference_logits_match_the_program_in_float32(family):
    import jax
    import jax.numpy as jnp

    cfg = CONFIGS[family]
    model, served = _program(cfg)
    f32 = model.cfg.with_(dtype="float32")
    from repro.models import get_model
    model32 = get_model(f32)
    tokens = np.random.default_rng(1).integers(1, cfg["vocab_size"], (2, 40),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(model32.prefill(
            served, {"tokens": jnp.asarray(tokens[:, :s])})[0])
            for s in range(33, 41)], axis=1)[..., :cfg["vocab_size"]]
    p = weights.make(reference.family(family).specs(cfg), SEED)
    got = np.asarray(jax.jit(lambda p, t: reference.family(family).logits(
        cfg, p, t, 32, 8, matmul.full))(p, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_control_in_float8_reads_wider_gaps_than_the_program(family):
    """The program (bfloat16, served through the cache) on a few seeds
    against the reference, and the reference's float8 control on the same
    prompts and tokens: the control's widest gap is several times the
    program's."""
    import jax.numpy as jnp

    from repro.serve import fabric
    from repro.serve.serve_step import generate

    cfg = CONFIGS[family]
    prog, ctrl = [], []
    for seed in (SEED, SEED + 1, SEED + 2):
        model, _ = _program(cfg)
        import jax
        served_params = model.init(jax.random.PRNGKey(weights.weight_seed(seed)))
        prompts = np.random.default_rng(seed).integers(
            1, cfg["vocab_size"], (8, 32), dtype=np.int32)
        toks = np.asarray(generate(model, served_params,
                                   {"tokens": jnp.asarray(prompts)}, 6))
        out = check.compare(cfg, seed, prompts, list(toks), 6, control=True)
        prog.append(check.widest(out["gaps"]))
        ctrl.append(check.widest(out["control"]))
    assert max(prog) < 0.05
    assert min(ctrl) > 3 * max(prog)
    del fabric
