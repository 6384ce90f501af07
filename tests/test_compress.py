"""Compression-layer tests: error feedback conservation, quantization
round-trip bounds."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

import pytest

from repro.compress import (int8_dequantize, int8_quantize, make_encode_step,
                            payload_nbytes, topk_compress, topk_decompress,
                            topk_init, topk_k)


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (64,)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (8, 16))}


def test_topk_sends_largest_and_conserves_mass():
    t = _tree()
    st0 = topk_init(t)
    payload, st1 = topk_compress(t, st0, frac=0.25)
    dense = topk_decompress(payload, t)
    # sent + residual == original (error feedback conserves the update)
    for k in t:
        np.testing.assert_allclose(np.asarray(dense[k] + st1.error[k]),
                                   np.asarray(t[k]), rtol=1e-6, atol=1e-6)
    # sent values are the largest-|v| entries
    sent = np.asarray(dense["a"])
    orig = np.abs(np.asarray(t["a"]))
    kept = sent != 0
    assert kept.sum() == 16
    assert orig[kept].min() >= np.sort(orig)[-16]


def test_topk_error_feedback_catches_up():
    """Repeated compression of a CONSTANT update converges to sending it
    fully (residual re-enters the selection)."""
    t = {"w": jnp.ones(100) * jnp.arange(1, 101)}
    st = topk_init(t)
    total = jnp.zeros(100)
    for _ in range(12):
        payload, st = topk_compress(t, st, frac=0.1)
        total = total + topk_decompress(payload, t)["w"] / 12
    # mean transmitted ≈ the true update for most coordinates
    err = float(jnp.abs(total - t["w"]).max() / t["w"].max())
    assert err < 0.5


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 100), scale=st.floats(0.01, 100.0))
def test_int8_roundtrip_error_bound(seed, scale):
    t = jax.tree.map(lambda x: x * scale, _tree(seed))
    qs, scales = int8_quantize(t)
    back = int8_dequantize(qs, scales, t)
    for k in t:
        step = float(scales[k])
        np.testing.assert_allclose(np.asarray(back[k]), np.asarray(t[k]),
                                   atol=step * 0.51)


def test_int8_dtype_and_size():
    t = _tree()
    qs, _ = int8_quantize(t)
    for k in t:
        assert qs[k].dtype == jnp.int8
        assert qs[k].shape == t[k].shape


def test_topk_k_exact_arithmetic():
    """k must come from exact integer arithmetic, not float truncation:
    int(100 * 0.29) == 28 is the classic hazard."""
    assert topk_k(100, 0.29) == 29
    assert topk_k(100, 0.01) == 1
    assert topk_k(100, 1.0) == 100
    assert topk_k(3, 0.001) == 1          # floor of 1
    assert topk_k(10, 0.05) == 1          # round-half-up of 0.5
    for size in (1, 7, 100, 4096):
        for frac in (0.01, 0.05, 0.1, 0.25, 0.5, 1.0):
            k = topk_k(size, frac)
            assert 1 <= k <= size


@pytest.mark.parametrize("frac", [0.0, -0.1, 1.5, float("nan")])
def test_topk_frac_out_of_range_rejected(frac):
    t = _tree()
    with pytest.raises(ValueError):
        topk_compress(t, topk_init(t), frac=frac)


def test_topk_frac_must_be_static():
    """A traced frac would make output shapes data-dependent — reject it
    eagerly with a clear message instead of a deep jit shape error."""
    t = _tree()
    with pytest.raises(TypeError, match="static"):
        topk_compress(t, topk_init(t), frac=jnp.float32(0.1))


# -- encode-step error-feedback conservation ---------------------------------

def _conservation(mode, frac=0.25):
    """sent + new_error == (theta - g) + old_error for the combine encoder."""
    g = _tree(3)
    theta = jax.tree.map(lambda x: x + 0.1 * jnp.sign(x), g)
    old_err = jax.tree.map(lambda x: 0.01 * x, _tree(4))
    encode = make_encode_step(mode, frac)
    payload, new_err = encode(g, theta, old_err)
    if mode == "int8":
        q, scales = payload
        sent = jax.tree.map(lambda qq, s: qq.astype(jnp.float32) * s, q, scales)
    else:
        like = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        sent = topk_decompress(payload, like)
    u = jax.tree.map(lambda t, gg, e: t - gg + e, theta, g, old_err)
    for k in g:
        np.testing.assert_allclose(np.asarray(sent[k] + new_err[k]),
                                   np.asarray(u[k]), rtol=1e-6, atol=1e-7)


def test_encode_step_conserves_update_int8():
    _conservation("int8")


def test_encode_step_conserves_update_topk():
    _conservation("topk")


def test_payload_nbytes_accounting():
    t = _tree()                                  # 64 + 128 elems, 2 leaves
    assert payload_nbytes(t, "int8", 0.0) == (64 + 4) + (128 + 4) + 8
    assert payload_nbytes(t, "topk", 0.25) == (16 + 32) * 8 + 8
    with pytest.raises(ValueError):
        payload_nbytes(t, "none", 0.0)
