"""Pallas kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.swiglu import swiglu


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,Hq,Hkv,hd", [
    (128, 4, 4, 64),     # MHA
    (256, 8, 2, 64),     # GQA 4:1
    (256, 4, 1, 128),    # MQA
    (128, 2, 2, 96),     # phi3-like head_dim
    (384, 8, 4, 256),    # gemma3-like head_dim (odd-multiple seq blocks)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(S, Hq, Hkv, hd, dtype):
    key = jax.random.PRNGKey(42)
    B = 2
    q = jax.random.normal(key, (B, S, Hq, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [64, 128, 1024])
def test_flash_attention_window(window):
    key = jax.random.PRNGKey(0)
    B, S, Hq, Hkv, hd = 1, 256, 4, 2, 64
    q = jax.random.normal(key, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    key = jax.random.PRNGKey(7)
    B, S, Hq, Hkv, hd = 2, 128, 4, 4, 80  # hubert-like
    q = jax.random.normal(key, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("C,length", [(512, 1), (512, 511), (1024, 700), (2048, 2048)])
@pytest.mark.parametrize("Hq,Hkv,hd", [(8, 2, 64), (4, 4, 128), (16, 2, 128)])
def test_decode_attention(C, length, Hq, Hkv, hd):
    key = jax.random.PRNGKey(3)
    B = 2
    q = jax.random.normal(key, (B, Hq, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, C, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, C, hd))
    out = decode_attention(q, kc, vc, jnp.int32(length), interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, jnp.int32(length))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,d,f", [(256, 256, 512), (512, 512, 2048), (128, 384, 1536)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu(T, d, f, dtype):
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (T, d), dtype)
    wg = (0.05 * jax.random.normal(jax.random.fold_in(key, 1), (d, f))).astype(dtype)
    wu = (0.05 * jax.random.normal(jax.random.fold_in(key, 2), (d, f))).astype(dtype)
    out = swiglu(x, wg, wu, interpret=True)
    expect = ref.swiglu_ref(x, wg, wu)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n,pref,align,want", [
    (512, 128, 8, 128), (520, 128, 8, 104), (96, 128, 8, 96),
    (1000, 256, 8, 200), (3072, 512, 128, 512), (3200, 512, 128, 128),
    (1100, 512, 128, None)])
def test_ops_block_divides_the_shape(n, pref, align, want):
    from repro.kernels import ops

    assert ops._block(n, pref, align) == want


def test_ops_non_dividing_shapes_run_the_kernel(monkeypatch):
    """Shapes the default tiles do not divide get a tile that does (S=520,
    T=1000) instead of tripping the kernels' divisibility asserts."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.kernels import ops

    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 520, 2, 64))
               for i in range(3))
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v)),
        np.asarray(ref.flash_attention_ref(q, k, v)), rtol=2e-5, atol=2e-5)
    x = jax.random.normal(key, (1000, 256))
    wg = 0.05 * jax.random.normal(jax.random.fold_in(key, 4), (256, 384))
    wu = 0.05 * jax.random.normal(jax.random.fold_in(key, 5), (256, 384))
    np.testing.assert_allclose(np.asarray(ops.swiglu(x, wg, wu)),
                               np.asarray(ref.swiglu_ref(x, wg, wu)),
                               rtol=2e-5, atol=2e-5)


def test_ops_refuses_interpret_on_tpu(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpreter on the TPU"):
        ops.kernel_mode()
    monkeypatch.setenv("REPRO_KERNEL_MODE", "auto")
    assert ops.kernel_mode() == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_MODE", "fast")
    with pytest.raises(ValueError, match="expected one of"):
        ops.kernel_mode()


def test_ops_dispatch_ref_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 64, 2, 32))
    k = jax.random.normal(key, (1, 64, 2, 32))
    v = jax.random.normal(key, (1, 64, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True)
    assert out.shape == q.shape
