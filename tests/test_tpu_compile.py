"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler runs here against a described ``v5e:2x2`` topology and
refuses what the chip would refuse: a Pallas block shape off the tiling,
a kernel it cannot lower, a program that does not fit the device. Only
shapes are passed; nothing runs. The topology is described inside a
fixture, never at import, so every test worker collects the same tests
and only the one given this file loads the TPU library.

Not covered while they fail to compile for the chip (ROADMAP 1.2):
``decode_attention_kernel``, ``rglru_scan_kernel`` (its compile aborts
the process) and ``ssd_scan_kernel``.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import get_model
from repro.serve import fabric

DEVICE_BYTES = 16 * 2**30           # one v5e chip's HBM
QWEN = "qwen1.5-0.5b"
DEEPSEEK = "deepseek-v2-lite@ep8"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("seq", [128, 512])
@pytest.mark.parametrize("block", [64, 128])
def test_flash_attention_compiles_at_qwen_prefill_width(one_chip, seq, block):
    cfg = get_config(QWEN)
    q = jax.ShapeDtypeStruct((1, seq, cfg.n_heads, cfg.head_dim_),
                             jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            block_q=block, block_k=block,
                                            interpret=False)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fabric_steps(one_chip, arch, bucket):
    """The prefill/decode pair of ``arch``, built as the fabric builds
    it, with abstract params in the compute dtype the fabric keeps them
    in, and inputs, on the described chip."""
    model = get_model(get_config(arch))
    prefill, decode = fabric.build_steps(model, bucket)
    params = _on(one_chip, jax.eval_shape(model.compute_params,
                                          model.abstract_params()))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((1, bucket),
                                                          jnp.int32)})
    _, cache = jax.eval_shape(prefill, params, batch)
    step_batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((1, 1),
                                                               jnp.int32)})
    return {"prefill": (prefill, (params, batch)),
            "decode": (decode, (params, _on(one_chip, cache), step_batch))}


@pytest.fixture(scope="module")
def fabric_steps(one_chip):
    """The published qwen1.5-0.5b at a 64-token bucket, and one chip's
    expert-parallel share of DeepSeek-V2-Lite at the 2,048-token bucket
    its benchmark cell serves."""
    return {"qwen": _fabric_steps(one_chip, QWEN, 64),
            "deepseek": _fabric_steps(one_chip, DEEPSEEK, 2048)}


@pytest.mark.parametrize("model,step", [
    pytest.param("qwen", "prefill", id="prefill"),
    pytest.param("qwen", "decode", id="decode"),
    pytest.param("deepseek", "prefill", id="deepseek-v2-lite@ep8-prefill"),
    pytest.param("deepseek", "decode", id="deepseek-v2-lite@ep8-decode"),
])
def test_fabric_step_compiles_and_fits_one_chip(fabric_steps, model, step):
    fn, args = fabric_steps[model][step]
    mem = fn.lower(*args).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < DEVICE_BYTES, used


def test_deepseek_decode_reads_no_whole_layer_of_held_experts(fabric_steps):
    """At one token the expert layer loops over the held experts it was
    routed to, each read at ``(layer, expert)`` of the stacked weights: no
    op outputs a layer's slice of all 8 held experts (the grouped
    product's dense lowering did, and so does a loop that indexes the
    scan's per-layer slice), and the temporaries stay small."""
    fn, args = fabric_steps["deepseek"]["decode"]
    compiled = fn.lower(*args).compile()
    whole = re.findall(r"bf16\[8,(?:2048,1408|1408,2048)\]",
                       compiled.as_text())
    assert not whole, len(whole)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 8 * 2**20, temp
