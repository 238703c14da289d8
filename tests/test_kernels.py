"""Per-kernel shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------- flash attn

@pytest.mark.parametrize("B,Sq,Sk,H,KVH,D", [
    (1, 64, 64, 4, 4, 32),       # MHA, square
    (2, 128, 128, 8, 2, 64),     # GQA 4:1
    (1, 96, 200, 4, 1, 64),      # MQA, ragged kv
    (2, 1, 160, 8, 4, 128),      # decode-style single query
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, Sq, Sk, H, KVH, D, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (B, Sq, H, D), dtype)
    k = rand(k2, (B, Sk, KVH, D), dtype)
    v = rand(k3, (B, Sk, KVH, D), dtype)
    off = Sk - Sq
    out = ops.flash_attention(q, k, v, causal=True, q_offset=off,
                              block_q=64, block_k=64, interpret=True)
    exp = ref.ref_attention(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (2, 128, 4, 32))
    k = rand(k2, (2, 128, 2, 32))
    v = rand(k3, (2, 128, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
    exp = ref.ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)


def test_flash_attention_noncausal():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (1, 48, 4, 64))
    k = rand(k2, (1, 72, 4, 64))
    v = rand(k3, (1, 72, 4, 64))
    out = ops.flash_attention(q, k, v, causal=False, block_q=16,
                              block_k=24, interpret=True)
    exp = ref.ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=3e-5)


def test_flash_matches_chunked_jnp_path():
    """The model's default chunked-jnp attention and the Pallas kernel are
    interchangeable implementations of the same contract."""
    from repro.models.common import chunked_attention
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (2, 100, 8, 64))
    k = rand(k2, (2, 100, 4, 64))
    v = rand(k3, (2, 100, 4, 64))
    a = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True)
    b = chunked_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------- decode attention

@pytest.mark.parametrize("B,H,KVH,D,S,block", [
    (2, 4, 4, 32, 128, 32),      # MHA
    (3, 8, 2, 64, 300, 64),      # GQA, ragged cache
    (1, 4, 1, 128, 1024, 256),   # MQA, long cache
])
def test_decode_attention_kernel(B, H, KVH, D, S, block):
    from repro.models.common import decode_attention as jnp_decode
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = rand(k1, (B, 1, H, D))
    kc = rand(k2, (B, S, KVH, D))
    vc = rand(k3, (B, S, KVH, D))
    lengths = jax.random.randint(k4, (B,), 1, S + 1)
    out = ops.decode_attention(q, kc, vc, lengths, block_s=block,
                               interpret=True)
    exp = jnp_decode(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=3e-5, rtol=3e-5)


def test_decode_attention_kernel_window():
    from repro.models.common import decode_attention as jnp_decode
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (2, 1, 4, 64))
    kc = rand(k2, (2, 256, 2, 64))
    vc = rand(k3, (2, 256, 2, 64))
    lengths = jnp.array([256, 100], jnp.int32)
    out = ops.decode_attention(q, kc, vc, lengths, window=64, block_s=64,
                               interpret=True)
    exp = jnp_decode(q, kc, vc, lengths, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------- RG-LRU

@pytest.mark.parametrize("B,S,W", [(1, 64, 128), (2, 100, 96), (3, 17, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_shapes(B, S, W, dtype):
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(rand(k1, (B, S, W))).astype(dtype)
    b = rand(k2, (B, S, W), dtype)
    out = ops.rglru(a, b, block_s=32, block_w=64, interpret=True)
    exp = ref.ref_rglru(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol(dtype))


def test_rglru_matches_associative_scan():
    from jax import lax
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(rand(k1, (2, 64, 128)))
    b = rand(k2, (2, 64, 128))
    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2
    _, exp = lax.associative_scan(combine, (a, b), axis=1)
    out = ops.rglru(a, b, interpret=True)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------- SSD

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 70, 4, 32, 64, 32),      # ragged
    (1, 256, 2, 64, 128, 128),   # production-ish tile
])
def test_ssd_shapes(B, S, H, P, N, chunk):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = rand(k1, (B, S, H, P))
    a = -jax.nn.softplus(rand(k2, (B, S, H)))
    Bm = rand(k3, (B, S, H, N))
    Cm = rand(k4, (B, S, H, N))
    y, st = ops.ssd(x, a, Bm, Cm, chunk=chunk, interpret=True)
    ye, ste = ref.ref_ssd(x, a, Bm, Cm)
    np.testing.assert_allclose(y, ye, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(st, ste, atol=5e-4, rtol=5e-4)


def test_ssd_matches_model_chunked_scan():
    from repro.models.ssm import ssd_scan
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    B, S, H, P, N = 2, 96, 2, 16, 32
    x = rand(k1, (B, S, H, P))
    a = -jax.nn.softplus(rand(k2, (B, S, H)))
    Bm = rand(k3, (B, S, H, N))
    Cm = rand(k4, (B, S, H, N))
    y1, s1 = ops.ssd(x, a, Bm, Cm, chunk=32, interpret=True)
    y2, s2 = ssd_scan(x, a, Bm, Cm, chunk=32)
    np.testing.assert_allclose(y1, y2, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(s1, s2, atol=5e-4, rtol=5e-4)


# ----------------------------------------------------- no silent interpret

@pytest.mark.parametrize("call", [
    lambda: ops.flash_attention(*[jnp.zeros((1, 64, 4, 64))] * 3,
                                block_q=64, block_k=64),
    lambda: ops.decode_attention(jnp.zeros((1, 1, 4, 64)),
                                 *[jnp.zeros((1, 64, 4, 64))] * 2,
                                 jnp.full((1,), 64), block_s=64),
    lambda: ops.rglru(*[jnp.zeros((1, 64, 128))] * 2, block_s=64),
    lambda: ops.ssd(jnp.zeros((1, 64, 2, 16)), jnp.zeros((1, 64, 2)),
                    *[jnp.zeros((1, 64, 2, 16))] * 2, chunk=32),
], ids=["flash_attention", "decode_attention", "rglru", "ssd"])
def test_compiled_kernel_refuses_the_cpu(call):
    """Interpret mode runs only when asked for: a compiled call off the
    TPU fails instead of quietly running the interpreter."""
    with pytest.raises(ValueError, match="interpret mode"):
        call()
