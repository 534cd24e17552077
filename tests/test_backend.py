"""Compiled execution plane: substrate resolution, compiled-vs-interpret
bitwise parity, buffer donation, break-even derivation, provenance tags.

Parity methodology: operands are integer-valued floats with small magnitude,
so every f32 accumulation is EXACT regardless of summation order — the
compiled tier (XLA lowerings on CPU, compiled Pallas on TPU) is asserted
BITWISE equal to the interpret-mode Pallas oracle, not allclose. The four
regimes pinned here are the ones the dispatch logic branches on: all-skip,
no-skip, ragged per-row counts, and the budget-overflow fallback.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import RAGGED_BREAK_EVEN_SKIP
from repro.core.reuse_linear import _interpret_arg
from repro.core.similarity import block_zero_mask
from repro.kernels import backend, ops
from repro.obs.latency import TAG_FIELDS, LatencyTable, table_provenance
from repro.roofline.model_cost import (
    predict_kernel_speedup,
    predicted_break_even_skip,
    reuse_kernel_cost,
)
from repro.roofline.validate import validate_kernel_sweep
from repro.tune.harvest import derive_break_even_skip


# ---------------------------------------------------------------------------
# substrate resolution
# ---------------------------------------------------------------------------


def test_best_is_compiled_and_cached():
    sub = backend.best()
    assert sub.compiled
    assert sub is backend.best()  # one resolution per process


def test_resolve_modes():
    assert backend.resolve(None) is backend.best()
    assert backend.resolve(True) is backend.INTERPRET
    assert not backend.INTERPRET.compiled
    if backend.best().use_pallas:
        assert backend.resolve(False) is backend.best()
    else:
        # no compiled Pallas on this host: explicit interpret=False must
        # raise, never silently interpret
        with pytest.raises(ValueError, match="no compiled Pallas"):
            backend.resolve(False)


def test_for_impl_mapping():
    assert backend.for_impl("jnp") is backend.XLA
    assert backend.for_impl("pallas_interpret") is backend.INTERPRET
    assert backend.for_impl("pallas").compiled  # degrades, never interprets
    with pytest.raises(ValueError):
        backend.for_impl("mystery")


def test_check_kernels_refuses_a_host_without_compiled_pallas():
    """`serve --check-kernels` has no fallback: on a host whose "pallas"
    impl resolves to the compiled-XLA tier the check fails before it reads
    any HLO."""
    from repro.configs import get_config
    from repro.launch.serve import require_pallas
    from repro.serve.serve_step import build_reuse_engine

    engine = build_reuse_engine(get_config("qwen3-32b").reduced(),
                                impl="pallas")
    if backend.for_impl("pallas") is backend.PALLAS:
        pytest.skip("this host compiles Pallas")
    with pytest.raises(RuntimeError, match="not compiled Pallas"):
        require_pallas(engine, "")


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    launchers cache in `.jax_cache/` at the checkout root. Either way an
    entry's key holds the program's metadata, its named scopes."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = compile_cache.DEFAULT_DIR
        assert compile_cache.enable_compile_cache() == str(default)
        assert jax.config.jax_compilation_cache_dir == str(default)
        assert default.name == ".jax_cache"
        assert (default.parent / "pyproject.toml").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)


def test_interpret_arg_threading():
    # the one explicit value reuse_linear threads into every kernel wrapper
    assert _interpret_arg("pallas_interpret") is True
    assert _interpret_arg("jnp") is None
    assert _interpret_arg("pallas") is None


def test_tag_fields():
    t = backend.tag()
    assert set(t) == set(TAG_FIELDS)
    assert t["backend"] == backend.best().name
    assert t["interpret"] is False
    it = backend.tag(backend.INTERPRET)
    assert it["backend"] == "interpret" and it["interpret"] is True


# ---------------------------------------------------------------------------
# compiled-vs-interpret bitwise parity (4 regimes)
# ---------------------------------------------------------------------------

M, K, N, BM, BN, BK = 16, 512, 256, 8, 128, 128
GK = K // BK


def _operands(rng, keep_prob):
    delta = rng.integers(-2, 3, size=(M, K)).astype(np.float32)
    for i in range(M // BM):
        for j in range(GK):
            if rng.random() >= keep_prob:
                delta[i * BM:(i + 1) * BM, j * BK:(j + 1) * BK] = 0.0
    w = rng.integers(-3, 4, size=(K, N)).astype(np.float32)
    prev = rng.integers(-5, 6, size=(M, N)).astype(np.float32)
    return jnp.asarray(delta), jnp.asarray(w), jnp.asarray(prev)


# keep_prob, ragged budget (None = occupancy-sized, no overflow)
REGIMES = [
    pytest.param(0.0, None, id="all-skip"),
    pytest.param(1.0, None, id="no-skip"),
    pytest.param(0.5, None, id="ragged-counts"),
    pytest.param(0.5, 1, id="overflow-fallback"),
]


@pytest.mark.parametrize("keep,budget", REGIMES)
def test_masked_kernel_parity(rng, keep, budget):
    delta, w, prev = _operands(rng, keep)
    mask = block_zero_mask(delta, BM, BK)
    compiled = ops.reuse_matmul(
        delta, w, prev, mask, block_m=BM, block_n=BN, block_k=BK)
    oracle = ops.reuse_matmul(
        delta, w, prev, mask, block_m=BM, block_n=BN, block_k=BK,
        interpret=True)
    assert bool(jnp.all(compiled == oracle))
    assert bool(jnp.all(
        compiled == ops.reuse_matmul_ref(delta, w, prev, mask, BM, BK)))


@pytest.mark.parametrize("keep,budget", REGIMES)
def test_ragged_parity(rng, keep, budget):
    delta, w, prev = _operands(rng, keep)
    mask = block_zero_mask(delta, BM, BK)
    counts = np.asarray(mask).sum(axis=1)
    if budget is None:
        budget = max(1, int(counts.max()))
    else:
        # the overflow regime must actually overflow: per-row active blocks
        # exceed the budget so the lax.cond fallback engages
        assert int(counts.max()) > budget
    kw = dict(block_m=BM, block_n=BN, block_k=BK, max_active_k=budget)
    compiled = ops.reuse_matmul_ragged(delta, w, prev, mask, **kw)
    oracle = ops.reuse_matmul_ragged(delta, w, prev, mask, **kw,
                                     interpret=True)
    assert bool(jnp.all(compiled == oracle))
    assert bool(jnp.all(
        compiled == ops.reuse_matmul_ref(delta, w, prev, mask, BM, BK)))


def test_delta_quant_parity(rng):
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    prev_q = jnp.asarray(rng.integers(-80, 80, size=(M, K)).astype(np.int8))
    scale = jnp.float32(0.05)
    kw = dict(block_m=BM, block_k=BK)
    q_c, d_c, m_c = ops.delta_quant_fused(x, prev_q, scale, **kw)
    q_i, d_i, m_i = ops.delta_quant_fused(x, prev_q, scale, **kw,
                                          interpret=True)
    assert bool(jnp.all(q_c == q_i))
    assert bool(jnp.all(d_c == d_i))
    assert bool(jnp.all(m_c == m_i))


def test_int8_parity(rng):
    delta, w, prev = _operands(rng, 0.5)
    dq = delta.astype(jnp.int8)
    wq = w.astype(jnp.int8)
    acc = jnp.zeros((M, N), jnp.int32)
    mask = block_zero_mask(delta, BM, BK)
    kw = dict(block_m=BM, block_n=BN, block_k=BK)
    compiled = ops.reuse_matmul_int8(dq, wq, acc, mask, **kw)
    oracle = ops.reuse_matmul_int8(dq, wq, acc, mask, **kw, interpret=True)
    assert bool(jnp.all(compiled == oracle))


# ---------------------------------------------------------------------------
# buffer donation (the serve step donates serve-state + reuse cache)
# ---------------------------------------------------------------------------


def test_donated_cache_buffer_is_consumed():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(cache, x):
        return {"prev": cache["prev"] + x}

    cache = {"prev": jnp.arange(1024, dtype=jnp.float32)}
    buf = cache["prev"]
    out = step(cache, jnp.float32(1.0))
    jax.block_until_ready(out)
    # donation consumed the input buffer: the old cache pytree is dead, its
    # storage was handed to the output instead of a fresh allocation
    assert buf.is_deleted()
    assert bool(jnp.all(out["prev"] == jnp.arange(1024) + 1.0))


def test_undonated_buffer_survives():
    @jax.jit
    def step(cache, x):
        return {"prev": cache["prev"] + x}

    cache = {"prev": jnp.arange(16, dtype=jnp.float32)}
    jax.block_until_ready(step(cache, jnp.float32(1.0)))
    assert not cache["prev"].is_deleted()


# ---------------------------------------------------------------------------
# measured break-even derivation + gate
# ---------------------------------------------------------------------------


def test_derive_break_even_empty_falls_back():
    assert derive_break_even_skip([]) == RAGGED_BREAK_EVEN_SKIP


def test_derive_break_even_interpolates_crossing():
    pts = [(0.0, 2.0, 1.0), (0.5, 1.0, 1.0), (1.0, 0.5, 1.0)]
    assert derive_break_even_skip(pts) == pytest.approx(0.5)
    pts = [(0.0, 1.5, 1.0), (0.5, 0.5, 1.0)]  # crossing inside the segment
    assert derive_break_even_skip(pts) == pytest.approx(0.25)


def test_derive_break_even_never_wins_codes_two():
    pts = [(s, 2.0, 1.0) for s in (0.0, 0.5, 0.9)]
    assert derive_break_even_skip(pts) == 2.0


def test_derive_break_even_wins_everywhere():
    pts = [(0.1, 0.5, 1.0), (0.9, 0.2, 1.0)]
    assert derive_break_even_skip(pts) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# latency-table provenance
# ---------------------------------------------------------------------------


def _table(*tag_rows, meta=None):
    t = LatencyTable()
    for i, tags in enumerate(tag_rows):
        t.record("site", None, f"path{i}", 1e-4, tags=tags)
    if meta:
        t.meta.update(meta)
    return t


def test_provenance_untagged_is_unknown():
    assert table_provenance(_table(None)) == "unknown"


def test_provenance_compiled_interpret_mixed():
    compiled = backend.tag()
    interp = backend.tag(backend.INTERPRET)
    assert table_provenance(_table(compiled)) == "compiled"
    assert table_provenance(_table(interp)) == "interpret"
    assert table_provenance(_table(compiled, interp)) == "mixed"


def test_provenance_meta_fallback():
    assert table_provenance(_table(None, meta={"interpret": True})) \
        == "interpret"
    assert table_provenance(_table(None, meta={"interpret": False})) \
        == "compiled"


def test_roundtrip_preserves_tags(tmp_path):
    from repro.obs.latency import load_latency_table

    t = _table(backend.tag())
    path = tmp_path / "latency_table.json"
    t.save(str(path))
    assert table_provenance(load_latency_table(str(path))) == "compiled"


# ---------------------------------------------------------------------------
# roofline kernel work model + sweep validation
# ---------------------------------------------------------------------------


def test_parity_paths_cost_dense_work():
    dense = reuse_kernel_cost(64, 2048, 256, path="dense", block_k=256)
    for p in ("kernel", "masked"):
        c = reuse_kernel_cost(64, 2048, 256, path=p, skip=0.9, block_k=256)
        assert c.flops == dense.flops and c.bytes == dense.bytes


def test_compact_speedup_monotone_in_skip():
    ups = [predict_kernel_speedup(64, 2048, 256, path="compact", skip=s,
                                  block_k=256)
           for s in (0.0, 0.25, 0.5, 0.75, 0.9)]
    assert all(b >= a for a, b in zip(ups, ups[1:]))
    assert ups[0] < 1.0 < ups[-1]  # gather overhead loses at 0, wins at 0.9


def test_predicted_break_even_in_sweep_range():
    be = predicted_break_even_skip(64, 2048, 256, path="compact",
                                   block_k=256)
    assert 0.0 < be < 1.0


def test_ragged_xla_group_duplication_can_never_win():
    # per-M-group weight gather on the XLA tier: at gm=8 the duplicated
    # traffic swamps the savings at every skip level
    be = predicted_break_even_skip(64, 2048, 256, path="ragged",
                                   block_m=8, block_k=256)
    assert be == 2.0


def _sweep_rows(us_by_path):
    rows = []
    for skip, paths in us_by_path.items():
        for path, us in paths.items():
            rows.append({
                "skip": skip, "path": path, "us": us,
                "m": 64, "k": 2048, "n": 256, "block_m": 8, "block_k": 256,
                "max_active_k": None if path != "ragged" else 8,
            })
    return rows


def test_validate_kernel_sweep_model_consistent():
    # measurements manufactured FROM the model: every check must pass
    us = {}
    for skip in (0.0, 0.25, 0.5, 0.75, 0.9):
        us[skip] = {"dense_gemm": 100.0}
        for p in ("compact", "ragged"):
            pred = predict_kernel_speedup(64, 2048, 256, path=p, skip=skip,
                                          block_k=256, max_active_k=8
                                          if p == "ragged" else None)
            us[skip][p] = 100.0 / pred
    rep = validate_kernel_sweep(_sweep_rows(us))
    assert rep["ok"]
    assert rep["rank_ok"] and rep["direction_ok"]
    assert all(c == pytest.approx(1.0)
               for c in rep["rank_correlation"].values() if c is not None)


def test_validate_kernel_sweep_refutes_early_win():
    # measurement claims compaction wins at EVERY skip level — left of the
    # model's overhead-free lower bound, so the one-sided check must fail
    us = {skip: {"dense_gemm": 100.0, "compact": 50.0}
          for skip in (0.0, 0.25, 0.5, 0.75, 0.9)}
    rep = validate_kernel_sweep(_sweep_rows(us))
    assert not rep["ok"]
    assert not rep["break_even_within_tol"]

    # measured crossing RIGHT of the prediction (overhead shifts it late)
    # is exactly what the one-sided bound permits
    us = {skip: {"dense_gemm": 100.0,
                 "compact": 80.0 if skip >= 0.75 else 300.0 - 100.0 * skip}
          for skip in (0.0, 0.25, 0.5, 0.75, 0.9)}
    rep = validate_kernel_sweep(_sweep_rows(us))
    assert rep["break_even_within_tol"]
