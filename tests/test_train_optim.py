"""Training substrate: loss decreases, chunked xent == full xent, microbatch
accumulation equivalence, grad compression error feedback."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS
from repro.data.pipeline import SyntheticLMSource
from repro.models import forward, init_params
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.optim.compression import compress_with_feedback, decompress
from repro.train.train_step import (
    chunked_xent_loss,
    init_train_state,
    make_train_step,
)


def test_chunked_xent_equals_full(rng):
    cfg = ARCHS["qwen3-32b"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 64
    h = jnp.asarray(rng.normal(size=(b, s, cfg.d_model)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    labels = labels.at[:, -5:].set(-1)  # some masked

    chunked = chunked_xent_loss(params, cfg, h, labels)

    from repro.models.layers import apply_norm
    from repro.train.train_step import _head_weight

    hn = apply_norm(params["final_norm"], h, cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", hn, _head_weight(params),
                        preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = (labels >= 0).astype(jnp.float32)
    full = jnp.sum((logz - gold) * valid) / jnp.sum(valid)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)


def test_loss_decreases_over_steps():
    cfg = ARCHS["qwen3-32b"].reduced()
    src = SyntheticLMSource(vocab=cfg.vocab, seq_len=64, global_batch=4,
                            correlation=0.9)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=2e-3),
                                   total_steps=40, warmup_steps=2))
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in src.batch(i).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_microbatch_accumulation_matches_full_batch():
    cfg = ARCHS["qwen3-32b"].reduced()
    src = SyntheticLMSource(vocab=cfg.vocab, seq_len=32, global_batch=4)
    batch = {k: jnp.asarray(v) for k, v in src.batch(0).items()}
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    s_full = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))(state, batch)
    s_mb = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3), microbatch=2))(
        state, batch)
    for a, b_ in zip(jax.tree.leaves(s_full[0]["params"]),
                     jax.tree.leaves(s_mb[0]["params"])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=2e-4, atol=2e-5,
        )


def test_adamw_weight_decay_only_on_matrices():
    params = {"w": jnp.ones((4, 4)), "scale": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4, 4)), "scale": jnp.zeros((4,))}
    state = init_opt_state(params)
    cfg = AdamWConfig(lr=1.0, weight_decay=0.1)
    new_params, _, _ = adamw_update(cfg, params, grads, state)
    assert float(jnp.max(jnp.abs(new_params["w"] - 0.9))) < 1e-6
    np.testing.assert_allclose(np.asarray(new_params["scale"]), 1.0)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_compression_roundtrip_bounded(seed):
    rng = np.random.default_rng(seed)
    g = {"a": jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))}
    c, resid = compress_with_feedback(g, None)
    d = decompress(c)
    amax = float(jnp.max(jnp.abs(g["a"])))
    err = float(jnp.max(jnp.abs(d["a"] - g["a"])))
    assert err <= amax / 127.0 + 1e-6
    # residual holds exactly the quantization error
    np.testing.assert_allclose(
        np.asarray(resid["a"]), np.asarray(g["a"] - d["a"]), atol=1e-6
    )


def test_error_feedback_corrects_bias():
    """Repeatedly compressing the same gradient with feedback: the mean of
    the decompressed stream converges to the true gradient (unbiasedness)."""
    g = {"a": jnp.full((8, 8), 0.003, jnp.float32) * jnp.linspace(
        0.1, 1.0, 8)[None, :]}
    resid = None
    total = jnp.zeros((8, 8), jnp.float32)
    n = 50
    for _ in range(n):
        c, resid = compress_with_feedback(g, resid)
        total = total + decompress(c)["a"]
    mean = total / n
    np.testing.assert_allclose(
        np.asarray(mean), np.asarray(g["a"]), rtol=0.05, atol=1e-5
    )


def test_data_pipeline_determinism_and_sharding():
    a = SyntheticLMSource(vocab=100, seq_len=16, global_batch=8, seed=3)
    b = SyntheticLMSource(vocab=100, seq_len=16, global_batch=8, seed=3)
    np.testing.assert_array_equal(a.batch(5)["tokens"], b.batch(5)["tokens"])
    # host sharding: two hosts see disjoint deterministic slices
    h0 = SyntheticLMSource(vocab=100, seq_len=16, global_batch=8, seed=3,
                           n_hosts=2, host_id=0)
    h1 = SyntheticLMSource(vocab=100, seq_len=16, global_batch=8, seed=3,
                           n_hosts=2, host_id=1)
    assert h0.batch(0)["tokens"].shape == (4, 16)
    assert not np.array_equal(h0.batch(0)["tokens"], h1.batch(0)["tokens"])


def test_correlated_stream_has_token_similarity():
    src = SyntheticLMSource(vocab=1000, seq_len=64, global_batch=2,
                            correlation=0.7)
    t1 = src.batch(1)["tokens"]
    t2 = src.batch(2)["tokens"]
    sim = np.mean(t1 == t2)
    assert 0.55 < sim < 0.85
