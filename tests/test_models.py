"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates at reduced scale and runs one forward + one train step on
CPU with shape and finiteness assertions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import forward, init_decode_state, init_params, output_logits
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step

ARCH_NAMES = sorted(ARCHS)


def _inputs(cfg, b, s, with_labels=False):
    if cfg.frontend == "audio":
        d = {"embeds": jnp.asarray(
            np.random.default_rng(0).normal(size=(b, s, cfg.d_model)),
            jnp.float32)}
    else:
        d = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, size=(b, s)),
            jnp.int32)}
    if with_labels:
        d["labels"] = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab, size=(b, s)),
            jnp.int32)
    return d


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_and_finite(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 64
    h, _, _, _ = forward(params, cfg, _inputs(cfg, b, s))
    assert h.shape == (b, s, cfg.d_model)
    logits = output_logits(params, cfg, h)
    assert logits.shape == (b, s, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_one_train_step(arch):
    cfg = ARCHS[arch].reduced()
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    batch = _inputs(cfg, 2, 64, with_labels=True)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    moved = jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b_.astype(jnp.float32)))),
        state["params"], new_state["params"],
    )
    assert max(jax.tree.leaves(moved)) > 0.0


@pytest.mark.parametrize("arch", [a for a in ARCH_NAMES
                                  if ARCHS[a].family != "audio"])
def test_decode_step_shapes(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = 2
    state = init_decode_state(cfg, b, cache_len=96)
    tok = jnp.zeros((b, 1), jnp.int32) + 5
    h, new_state, _, _ = forward(params, cfg, {"tokens": tok},
                                 decode_state=state)
    assert h.shape == (b, 1, cfg.d_model)
    assert int(new_state["len"]) == 1
    assert bool(jnp.all(jnp.isfinite(h)))


@pytest.mark.parametrize("arch", [a for a in ARCH_NAMES
                                  if ARCHS[a].family != "audio"])
def test_prefill_then_decode_matches_full_forward(arch):
    """Serving correctness: prefill(prompt) + decode(next) must produce the
    same hidden states as one forward over the concatenated sequence.

    MoE archs run with dropless capacity here: capacity dropping is rank-
    order dependent across the token axis, so a 33-token forward and a
    32+1 prefill/decode legitimately drop different tokens otherwise."""
    import dataclasses

    cfg = ARCHS[arch].reduced()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 32
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(b, s + 1)), jnp.int32)

    # full forward over s+1 tokens (no cache)
    h_full, _, _, _ = forward(params, cfg, {"tokens": toks})

    # prefill s, then decode token s
    state = init_decode_state(cfg, b, cache_len=s + 8)
    h_pre, state, _, _ = forward(params, cfg, {"tokens": toks[:, :s]},
                                 decode_state=state)
    h_dec, state, _, _ = forward(params, cfg, {"tokens": toks[:, s:s + 1]},
                                 decode_state=state)
    np.testing.assert_allclose(
        np.asarray(h_dec[:, 0], np.float32),
        np.asarray(h_full[:, s], np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_vision_stub_merges_patch_embeddings():
    cfg = ARCHS["qwen2-vl-7b"].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s, p = 2, 32, 4
    rng = np.random.default_rng(0)
    inputs = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "vision_embeds": jnp.asarray(
            rng.normal(size=(b, p, cfg.d_model)), jnp.float32),
        "vision_positions": jnp.asarray(
            np.stack([np.arange(2, 2 + p)] * b), jnp.int32),
    }
    h, _, _, _ = forward(params, cfg, inputs)
    assert bool(jnp.all(jnp.isfinite(h)))
    # and the vision positions actually influence the output
    inputs2 = dict(inputs, vision_embeds=inputs["vision_embeds"] + 1.0)
    h2, _, _, _ = forward(params, cfg, inputs2)
    assert float(jnp.max(jnp.abs(h - h2))) > 0


def test_param_count_formulas():
    """Config param_count must track actual init within tolerance (embeddings
    + lora/norm slop) — used by the roofline's 6·N·D bookkeeping."""
    for arch in ARCH_NAMES:
        cfg = ARCHS[arch].reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        predicted = cfg.param_count()
        assert 0.5 < actual / predicted < 2.0, (
            arch, actual, predicted)


def test_with_layers_cuts_depth_only():
    """`serve --layers N`: the published config with only n_layers replaced,
    so every width, and with it every kernel shape, stays the real one."""
    import dataclasses

    cfg = ARCHS["qwen3-32b"]
    cut = cfg.with_layers(8)
    assert cut.n_layers == 8 and cut.n_superblocks == 8
    assert dataclasses.replace(cut, n_layers=cfg.n_layers) == cfg
    for bad in (0, -8):
        with pytest.raises(ValueError, match="multiple"):
            cfg.with_layers(bad)
    local_global = next(c for c in ARCHS.values()
                        if c.superblock_layers > 1)
    with pytest.raises(ValueError, match="superblock"):
        local_global.with_layers(local_global.superblock_layers + 1)

