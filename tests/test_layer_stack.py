"""Reuse sites that read their weight in place from the layer stack.

The reuse GEMM takes a weight `[L, K, N]` with a layer index and reads that
layer's tiles from the stack, so the decode scan hands the reuse sites their
stacked parameters instead of a per-layer copy. Every other consumer slices
`w[layer]` and runs as before. Here the stack-and-index call is held bitwise
to the same call on `w[layer]`: the kernel on its own, in interpret mode and
on the compiled-XLA tier, and whole decode steps against an evaluation in
which the scan slices every weight.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import MODE_BASIC
from repro.kernels import ops
from repro.models import init_params, transformer
from repro.serve.serve_step import (
    build_reuse_engine,
    decode_step,
    init_serve_state,
)

L, M, K, N, BM, BK, BN = 3, 16, 512, 256, 8, 256, 128
MASKS = {
    "all_live": np.ones((M // BM, K // BK), np.int32),
    "half_live": np.array([[1, 0], [0, 1]], np.int32),
    "all_skipped": np.zeros((M // BM, K // BK), np.int32),
}


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("dataflow", ["output", "input"])
@pytest.mark.parametrize("interpret", [True, None], ids=["interpret", "xla"])
def test_stack_call_equals_sliced_call(rng, interpret, dataflow, layer, mask):
    w = jnp.asarray(rng.normal(size=(L, K, N)), jnp.float32)
    delta = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    block_mask = jnp.asarray(MASKS[mask])
    kw = dict(block_m=BM, block_n=BN, block_k=BK, dataflow=dataflow,
              interpret=interpret)
    stacked = ops.reuse_matmul(delta, w, prev, block_mask,
                               layer=jnp.int32(layer), **kw)
    sliced = ops.reuse_matmul(delta, w[layer], prev, block_mask, **kw)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))
    if mask == "all_skipped":
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(prev))


@pytest.mark.parametrize("dataflow", ["output", "input"])
@pytest.mark.parametrize("interpret", [True, None], ids=["interpret", "xla"])
def test_unaligned_stack_call_equals_sliced_call(rng, interpret, dataflow):
    """A stack off the tile grid is sliced before it is padded."""
    k, n = K - 12, N - 56
    w = jnp.asarray(rng.normal(size=(L, k, n)), jnp.float32)
    delta = jnp.asarray(rng.normal(size=(M, k)), jnp.float32)
    prev = jnp.asarray(rng.normal(size=(M, n)), jnp.float32)
    kw = dict(block_m=BM, block_n=BN, block_k=BK, dataflow=dataflow,
              interpret=interpret)
    call = jax.jit(lambda w, layer: ops.reuse_matmul(
        delta, w, prev, jnp.asarray(MASKS["half_live"]), layer=layer, **kw))
    stacked = call(w, jnp.int32(L - 1))
    sliced = ops.reuse_matmul(delta, w[L - 1], prev,
                              jnp.asarray(MASKS["half_live"]), **kw)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))
    hlo = call.lower(w, jnp.int32(0)).as_text()
    assert f"tensor<{L}x{K}x{N}xf32>" not in hlo


def test_aligned_stack_is_not_padded():
    w = jnp.zeros((L, K, N), jnp.bfloat16)
    assert ops._pad_to(w, BK, BN) is w
    assert ops._pad_to(w[:, :K - 8, :N - 8], BK, BN).shape == (L, K, N)


# ---------------------------------------------------------------- decode step

BATCH, CACHE_LEN, STEPS = 8, 16, 3
ARCHS = ("qwen3-32b", "nemotron-4-15b", "nemotron-h-47b")


def _decode(cfg, engine, params, *, in_place: bool, monkeypatch):
    """STEPS greedy decode steps; the last with the last layer of every site
    pinned to basic through its ctrl lane. `in_place=False` is the evaluation
    in which the scan slices every weight and each site gets `w[layer]`."""
    with monkeypatch.context() as mp:
        if not in_place:
            mp.setattr(transformer, "_lift_site_weights",
                       lambda blocks, rcache: (blocks, {}))
        step = jax.jit(lambda p, t, s, r: decode_step(
            p, cfg, t, s, engine=engine, reuse_cache=r))
        state = init_serve_state(cfg, BATCH, CACHE_LEN)
        rcache = engine.init_cache(BATCH)
        tok = jnp.arange(BATCH, dtype=jnp.int32)[:, None]
        outs = []
        for i in range(STEPS):
            if i == STEPS - 1:
                rcache = {
                    name: dict(e, ctrl=dict(
                        e["ctrl"],
                        mode_id=e["ctrl"]["mode_id"].at[-1].set(MODE_BASIC)))
                    for name, e in rcache.items()}
            logits, state, rcache = step(params, tok, state, rcache)
            outs.append((logits, state, rcache))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return outs


def _assert_bitwise(a, b):
    leaves_a, tree_a = jax.tree.flatten(a)
    leaves_b, tree_b = jax.tree.flatten(b)
    assert tree_a == tree_b
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _check_decode(cfg, engine, monkeypatch):
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, stacks = transformer._lift_site_weights(
        params["blocks"], engine.init_cache(BATCH))
    assert len(stacks) == len(engine.sites)
    _assert_bitwise(
        _decode(cfg, engine, params, in_place=True, monkeypatch=monkeypatch),
        _decode(cfg, engine, params, in_place=False, monkeypatch=monkeypatch))


@pytest.mark.parametrize("impl", ["pallas_interpret", "pallas"])
@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_equals_sliced_weights(monkeypatch, arch, impl):
    cfg = get_config(arch).reduced()
    _check_decode(cfg, build_reuse_engine(cfg, impl=impl), monkeypatch)


@pytest.mark.parametrize("variant", ["ragged", "compact", "dense", "sharded"])
def test_decode_step_other_paths_slice_in_site(monkeypatch, variant):
    """The paths that do not read a stack in place slice it inside the site
    and serve exactly as when the scan sliced it."""
    cfg = get_config("qwen3-32b").reduced()
    engine = build_reuse_engine(cfg, impl="pallas")
    if variant == "sharded":
        engine.shard_sites(2)
    else:
        engine.sites = {name: dataclasses.replace(spec, exec_path=variant)
                        for name, spec in engine.sites.items()}
    _check_decode(cfg, engine, monkeypatch)
