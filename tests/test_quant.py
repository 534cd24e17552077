"""Quantization properties (hypothesis): the substrate the similarity
measurements stand on."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.quant import (
    QuantSpec,
    calibrate_scale,
    dequantize_int8,
    fake_quantize,
    quantize_int8,
)


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**16), scale=st.floats(1e-3, 10.0))
def test_roundtrip_error_bounded(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32)) * scale
    s = calibrate_scale(x)
    err = jnp.abs(dequantize_int8(quantize_int8(x, s), s) - x)
    assert float(jnp.max(err)) <= float(s) / 2 + 1e-7


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_symmetric_codes(seed):
    """q(-x) == -q(x): required for the delta algebra to be sign-stable."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    s = calibrate_scale(x)
    q_pos = np.asarray(quantize_int8(x, s), np.int32)
    q_neg = np.asarray(quantize_int8(-x, s), np.int32)
    np.testing.assert_array_equal(q_pos, -q_neg)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_identical_inputs_identical_codes(seed):
    """The premise of the whole paper: equal values -> equal codes, and small
    perturbations below scale/2 collapse onto the same code (that is WHY
    int8 models show such high similarity)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    s = calibrate_scale(x)
    eps = float(s) * 0.49
    x2 = x + eps * jnp.asarray(rng.uniform(-1, 1, size=(64,)).astype(np.float32))
    q1 = np.asarray(quantize_int8(x, s))
    q2 = np.asarray(quantize_int8(x2, s))
    assert np.mean(q1 == q2) > 0.4  # perturbation below half-step mostly collapses


def test_per_channel_scale_shape():
    x = jnp.ones((4, 8, 16))
    spec = QuantSpec(per_channel=True, channel_axis=-1)
    s = calibrate_scale(x, spec)
    assert s.shape == (16,)


def test_fake_quantize_idempotent():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32,)), jnp.float32)
    y = fake_quantize(x)
    # scale is recalibrated from y: max-abs preserved => same grid => fixpoint
    z = fake_quantize(y)
    np.testing.assert_allclose(np.asarray(y), np.asarray(z), atol=1e-6)
