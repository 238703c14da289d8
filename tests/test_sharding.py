"""Sharding rules: logical→mesh mapping, divisibility fallback, duplicate
axis prevention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import ShardCtx, default_rules, spec_for, tree_shardings


@pytest.fixture
def mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def test_spec_basic(mesh):
    rules = default_rules()
    s = spec_for(("embed", "ffn"), (1024, 4096), mesh, rules)
    assert s == P("data", "model")


def test_divisibility_fallback(mesh):
    rules = {"x": ("data",), "y": ("model",)}
    dev = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    m = Mesh(dev, ("data", "model"))
    # 10 not divisible by 4 → replicate that dim
    s = spec_for(("x", "y"), (10, 16), m, rules)
    assert s == P(None, "model")


def test_batch_one_replicates():
    """long_500k (batch=1) degrades to replication automatically."""
    dev = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    m = Mesh(dev, ("data", "model"))
    rules = default_rules()
    s = spec_for(("act_batch", None, None), (1, 524288, 64), m, rules)
    assert s == P()


def test_no_duplicate_mesh_axes(mesh):
    dev = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    m = Mesh(dev, ("data", "model"))
    rules = {"a": ("data",), "b": ("data", "model")}
    s = spec_for(("a", "b"), (8, 8), m, rules)
    # "data" already used by dim 0 → dim 1 only gets "model"
    assert s == P("data", "model")


def test_missing_mesh_axis_ignored(mesh):
    rules = {"batch": ("pod", "data")}        # no "pod" on this mesh
    dev = np.array(jax.devices() * 4)[:4].reshape(4,)
    m = Mesh(dev.reshape(4, 1), ("data", "model"))
    s = spec_for(("batch",), (8,), m, rules)
    assert s == P("data")


def test_multi_axis_prefix_fallback():
    """(pod,data)=8 doesn't divide 4 → falls back to the pod prefix (2)."""
    dev = np.array(jax.devices() * 8)[:8].reshape(2, 4, 1)
    m = Mesh(dev, ("pod", "data", "model"))
    rules = {"batch": ("pod", "data")}
    s = spec_for(("batch",), (4,), m, rules)
    # 4 % (2*4) != 0 but 4 % 2 == 0 → shard over pod only
    assert s == P("pod")


def test_tree_shardings_structure(mesh):
    rules = default_rules()
    axes = {"w": ("embed", "ffn"), "b": ("ffn",)}
    shapes = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32),
              "b": jax.ShapeDtypeStruct((128,), jnp.float32)}
    shd = tree_shardings(axes, shapes, mesh, rules)
    assert shd["w"].spec == P("data", "model")
    assert shd["b"].spec == P("model")


def test_shard_ctx_noop_without_mesh():
    ctx = ShardCtx()
    x = jnp.ones((4, 4))
    assert ctx.constrain(x, ("act_batch", None)) is x


def test_shard_ctx_constrain_compiles(mesh):
    ctx = ShardCtx(mesh, default_rules())
    @jax.jit
    def f(x):
        return ctx.constrain(x, ("act_batch", "act_embed")) * 2
    out = f(jnp.ones((4, 8)))
    np.testing.assert_array_equal(out, 2 * np.ones((4, 8)))


def test_policy_variants_differ():
    fsdp = default_rules("fsdp")
    tp = default_rules("fsdp_tp")
    dp = default_rules("dp")
    assert fsdp["act_heads"] == ()
    assert tp["act_heads"] == ("model",)
    assert dp["embed"] == ()
    with pytest.raises(ValueError):
        default_rules("nope")
