"""Property-based tests (hypothesis) for the system's central invariants.

The load-bearing property of the whole scheme (paper Eqns. 2-4): after any
number of reuse steps, the accumulated output equals the quantized dense
output of the *current* input — the deltas telescope. If this holds, reuse
can never change model outputs, only costs.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import ReuseEngine, block_zero_mask, delta_encode_int8
from repro.core.delta import compact_block_indices
from repro.core.similarity import harvestable_similarity
from repro.quant import dequantize_int8, quantize_int8


shapes = st.tuples(
    st.integers(1, 12),          # batch
    st.sampled_from([64, 128, 256]),   # in_features
    st.sampled_from([64, 128]),  # out_features
)


@settings(max_examples=20, deadline=None)
@given(shape=shapes, n_steps=st.integers(1, 5),
       similarity=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
def test_telescoping_invariant(shape, n_steps, similarity, seed):
    """reuse(x_1..x_t) == quantized_dense(x_t), for any stream."""
    b, k, n = shape
    rng = np.random.default_rng(seed)
    eng = ReuseEngine(impl="jnp")
    eng.register("site", k, n, block_m=8, block_k=64)
    cache = eng.init_cache(batch=b)["site"]
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * 0.1)

    x = rng.normal(size=(b, k)).astype(np.float32)
    for _ in range(n_steps):
        keep = rng.random((b, k)) < similarity
        x = np.where(keep, x, rng.normal(size=(b, k)).astype(np.float32))
        out, cache, _ = eng.apply("site", jnp.asarray(x), w, None, cache)

    xq = dequantize_int8(quantize_int8(jnp.asarray(x), cache["scale"]),
                         cache["scale"])
    dense = xq @ w
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=1e-4, atol=1e-4
    )


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_cold_start_equals_quantized_dense(seed):
    """First-ever call (zero cache) must already equal the quantized GEMM —
    no special-casing/branching needed (DESIGN.md §reuse_linear)."""
    rng = np.random.default_rng(seed)
    b, k, n = 4, 128, 64
    eng = ReuseEngine(impl="jnp")
    eng.register("site", k, n, block_m=8, block_k=64)
    cache = eng.init_cache(batch=b)["site"]
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    out, cache, _ = eng.apply("site", x, w, None, cache)
    xq = dequantize_int8(quantize_int8(x, cache["scale"]), cache["scale"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xq @ w), rtol=1e-4, atol=1e-4
    )


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_overflow_split_bounds_and_exactness(seed):
    rng = np.random.default_rng(seed)
    cur = jnp.asarray(rng.integers(-127, 128, size=(8, 128)), jnp.int8)
    prev = jnp.asarray(rng.integers(-127, 128, size=(8, 128)), jnp.int8)
    enc = delta_encode_int8(cur, prev, block_m=8, block_k=64)
    lo = enc.lo.astype(np.int32)
    hi = enc.hi.astype(np.int32)
    assert np.abs(np.asarray(lo)).max() <= 127
    assert np.abs(np.asarray(hi)).max() <= 127
    exact = np.asarray(cur, np.int32) - np.asarray(prev, np.int32)
    np.testing.assert_array_equal(np.asarray(lo) + np.asarray(hi), exact)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16), bm=st.sampled_from([4, 8]),
       bk=st.sampled_from([32, 64]))
def test_block_mask_covers_every_nonzero(seed, bm, bk):
    """mask == 0 for a tile ⟹ the tile is entirely zero (never drops data)."""
    rng = np.random.default_rng(seed)
    delta = rng.normal(size=(16, 128)) * (rng.random((16, 128)) < 0.1)
    mask = np.asarray(block_zero_mask(jnp.asarray(delta), bm, bk))
    for i in range(mask.shape[0]):
        for j in range(mask.shape[1]):
            tile = delta[i * bm:(i + 1) * bm, j * bk:(j + 1) * bk]
            if mask[i, j] == 0:
                assert np.all(tile == 0)
            else:
                assert np.any(tile != 0)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_harvestable_similarity_monotone_in_granularity(seed):
    """Coarser skip granularity can only harvest less similarity — the TPU
    analogue of the paper's sdot (13.9%) vs mla8 observation."""
    rng = np.random.default_rng(seed)
    cur = jnp.asarray(rng.integers(-4, 5, size=(32, 512)), jnp.int8)
    keep = rng.random((32, 512)) < 0.8
    prev = jnp.asarray(np.where(keep, np.asarray(cur), 0), jnp.int8)
    h = [
        float(harvestable_similarity(cur, prev, 1, bk))
        for bk in (1, 32, 128, 512)
    ]
    assert all(h[i] >= h[i + 1] - 1e-9 for i in range(len(h) - 1))
    # element-granularity harvest == raw similarity
    raw = float(jnp.mean((cur == prev).astype(jnp.float32)))
    assert abs(h[0] - raw) < 1e-6


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**16), gk=st.integers(1, 16))
def test_compact_block_indices(seed, gk):
    rng = np.random.default_rng(seed)
    mask = jnp.asarray(rng.integers(0, 2, size=(gk,)), jnp.int32)
    idx, count = compact_block_indices(mask)
    idx, count = np.asarray(idx), int(count)
    expected = np.nonzero(np.asarray(mask))[0]
    assert count == len(expected)
    np.testing.assert_array_equal(idx[:count], expected)
    if count:  # tail clamps to a valid (already-counted) block
        assert np.all(np.isin(idx[count:], expected))
