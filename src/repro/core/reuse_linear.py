"""reuse_linear — one reuse site: O_c = O_p + Δ·W (paper Eqns. 2-4).

Cold-start needs no branch: caches initialize to prev_q = 0, prev_out = 0, so
the first evaluation degenerates to O = dequant(quantize(x))·W — the ordinary
quantized GEMM. Every subsequent evaluation telescopes:

    O_t = Σ_{i<=t} Δ_i · W = dequant(q_t) · W        (exactly, in int32;
                                                      to f32 rounding in float)

so the reuse output always equals the quantized dense output — the central
correctness invariant, property-tested in tests/test_reuse_properties.py.

Reuse is an *inference* feature (the paper's setting): models enable it on
decode-step linear sites, where M = serving batch and the GEMM is deeply
memory-bound — precisely where skipping weight-tile DMAs pays.

kernelMode dispatch is ARRAY-RESIDENT: with `mode=None` (the engine's default)
the call branches with `lax.cond` on the cache entry's per-layer control block
(`cache["ctrl"]["mode_id"]`), so a scanned stack slices a per-layer mode out
of the cache exactly like it slices prev_q — one trace covers both modes for
every layer, and a host-side mode flip is an array write, never a retrace. A
string `mode` ("reuse" | "basic") keeps the static single-branch dispatch for
explicitly pinned sites, tests and benchmarks.

`impl` selects the execution substrate (resolved by kernels/backend.py):
    "jnp"              — pure-jnp semantics (fast on CPU; what the dry-run lowers)
    "pallas_interpret" — the real kernels, interpreted on CPU (EXPLICIT test mode)
    "pallas"           — best compiled substrate: compiled Pallas on TPU, the
                         compiled-XLA tier (kernels/xla_tier.py) on hosts with
                         no Pallas lowering — never silent interpret fallback

A site inside a scan over layers may take its weight as the whole layer
stack `[L, K, N]` with the scan's `layer` index: the reuse branch on the
"kernel" path hands both to `ops.reuse_matmul`, whose Pallas kernel reads a
tile-aligned stack's tiles in place; every other consumer slices `w[layer]`
first and runs as with a `[K, N]` weight.

`spec.exec_path` selects the reuse-mode GEMM within a substrate (see
kernels/ops.py): "kernel" masked full grid, "ragged" compacted grid,
"compact" jnp gather, "dense" jnp masked GEMM. "auto" preserves the historic
mapping (Pallas impls → "kernel", jnp → "dense"); the policy promotes sites
off it from measured skip rate. On the Pallas impls the quantize → delta →
tile-mask chain runs as ONE fused pass (kernels/delta_quant.py) instead of
the three-op jnp chain, so the delta tensor crosses HBM once.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.delta import DeltaEncoding, delta_encode
from repro.core.reuse_cache import ReuseSiteSpec, resolve_exec_path
from repro.core.similarity import ema_update, row_code_similarity
from repro.kernels import ops
from repro.kernels.ops import layer_weight
from repro.quant import dequantize_int8, quantize_int8
from repro.sensor.counters import (
    ShardCtx,
    owned_panel_count,
    update_on_basic,
    update_on_reuse,
)


class ReuseStats(NamedTuple):
    similarity: jax.Array     # code-level similarity this call
    skip_fraction: jax.Array  # fraction of weight tiles skipped this call


def _interpret_arg(impl: str) -> bool | None:
    """The ONE interpret value threaded into every kernel wrapper call.

    True only for the explicit interpret test mode; None otherwise, which
    `kernels.backend.resolve` turns into the best compiled substrate for this
    process (compiled Pallas on TPU, compiled-XLA elsewhere).
    """
    return True if impl == "pallas_interpret" else None


def _encode(
    xm: jax.Array, cache: dict[str, jax.Array], spec: ReuseSiteSpec,
    w_dtype, impl: str,
) -> DeltaEncoding:
    """Quantize + delta + tile mask: fused single pass on the Pallas impls,
    the jnp three-op chain otherwise."""
    if impl == "jnp":
        return delta_encode(
            xm, cache["prev_q"], cache["scale"],
            block_m=spec.block_m, block_k=spec.block_k,
            compute_dtype=w_dtype,
        )
    cur_q, delta, mask = ops.delta_quant_fused(
        xm, cache["prev_q"], cache["scale"],
        block_m=spec.block_m, block_k=spec.block_k,
        delta_dtype=w_dtype, interpret=_interpret_arg(impl),
    )
    skip = 1.0 - jnp.mean(mask.astype(jnp.float32))
    return DeltaEncoding(delta=delta, cur_q=cur_q, block_mask=mask,
                         skip_fraction=skip)


def _basic_eval(
    xm: jax.Array, w: jax.Array, cache: dict[str, jax.Array],
    spec: ReuseSiteSpec, ema_decay: float,
    shard: ShardCtx | None = None, layer: jax.Array | None = None,
):
    """ReuseSensor+ReuseOFF: the generated basic kernel (Fig. 7-A) — plain
    quantized GEMM, no delta/cache bookkeeping beyond refreshing state."""
    w = layer_weight(w, layer)
    m, k = xm.shape
    n = w.shape[-1]
    cur_q = quantize_int8(xm, cache["scale"])
    out = jnp.dot(
        dequantize_int8(cur_q, cache["scale"], dtype=xm.dtype),
        w,
        preferred_element_type=jnp.float32,
    )
    row_sim = row_code_similarity(cur_q, cache["prev_q"])
    sim = jnp.mean(row_sim)
    new_cache = dict(
        cache,
        prev_q=cur_q,
        prev_out=out,
        sim_ema=ema_update(cache["sim_ema"], row_sim, ema_decay),
        steps=cache["steps"] + 1,
    )
    if "sensor" in cache:
        new_cache["sensor"] = update_on_basic(
            cache["sensor"], row_sim=row_sim, m=m, k=k, n=n,
            gn=-(-n // spec.block_n),
            block_m=spec.block_m, block_k=spec.block_k,
            w_itemsize=w.dtype.itemsize,
            shard=shard,
        )
    stats = ReuseStats(similarity=sim,
                       skip_fraction=jnp.zeros((), jnp.float32))
    return out, new_cache, stats


def _reuse_eval(
    xm: jax.Array, w: jax.Array, cache: dict[str, jax.Array],
    spec: ReuseSiteSpec, impl: str, ema_decay: float,
    shard: ShardCtx | None = None, layer: jax.Array | None = None,
):
    """ReuseSensor+ReuseON: delta-encode against the previous evaluation and
    run the ΔW GEMM on the spec's execution substrate.

    With `shard` set the GEMM itself is untouched (w/prev_out are already the
    shard-local [K, N/S] slices) — only the dma/grid accounting changes:
    every per-panel formula is linear in the n-panel count, so it is
    evaluated at gn=1 and scaled by the shard's owned GLOBAL panel count
    (counters.py ownership partition; the sum over shards is bitwise the
    unsharded value)."""
    n = w.shape[-1]
    enc = _encode(xm, cache, spec, w.dtype, impl)
    path = resolve_exec_path(spec, impl)
    if path != "kernel":
        # only the masked full-grid kernel reads a layer stack in place
        w = layer_weight(w, layer)
    gm, gk = enc.block_mask.shape
    gn = -(-n // spec.block_n)
    gn_own = None if shard is None else owned_panel_count(shard)
    interpret = _interpret_arg(impl)
    sel = None
    dma_issued = None
    grid_steps = None
    overflow = None
    if path == "dense":
        out = ops.reuse_matmul_ref(
            enc.delta, w, cache["prev_out"], enc.block_mask,
            spec.block_m, spec.block_k,
        )
    elif path == "compact":
        k_mask = jnp.max(enc.block_mask, axis=0)
        out = ops.reuse_matmul_compact(
            enc.delta, w, cache["prev_out"], k_mask,
            block_k=spec.block_k, max_blocks=spec.max_active_k,
        )
        # The gather streams each live K-block's weight panel once,
        # shared across all rows.
        if shard is None:
            dma_issued = jnp.sum(k_mask).astype(jnp.int32) * gn
            grid_steps = ops.ragged_grid_steps(
                jnp.broadcast_to(jnp.sum(k_mask), (gm,)),
                gm=gm, gn=gn, gk=gk, max_active_k=spec.max_active_k,
            )
        else:
            dma_issued = jnp.sum(k_mask).astype(jnp.int32) * gn_own
            grid_steps = ops.ragged_grid_steps(
                jnp.broadcast_to(jnp.sum(k_mask), (gm,)),
                gm=gm, gn=1, gk=gk, max_active_k=spec.max_active_k,
            ) * gn_own.astype(jnp.float32)
        overflow = ops.budget_overflow(
            jnp.sum(k_mask), gk=gk, max_active_k=spec.max_active_k
        )
    elif path == "ragged":
        idx, counts = ops.compact_rows(enc.block_mask)
        out = ops.reuse_matmul_ragged(
            enc.delta, w, cache["prev_out"], enc.block_mask,
            block_m=spec.block_m, block_n=spec.block_n,
            block_k=spec.block_k, max_active_k=spec.max_active_k,
            interpret=interpret, compacted=(idx, counts),
        )
        if shard is None:
            dma_issued = ops.ragged_dma_tiles(counts, gn=gn)
            grid_steps = ops.ragged_grid_steps(
                counts, gm=gm, gn=gn, gk=gk, max_active_k=spec.max_active_k,
            )
        else:
            dma_issued = ops.ragged_dma_tiles(counts, gn=1) * gn_own
            grid_steps = ops.ragged_grid_steps(
                counts, gm=gm, gn=1, gk=gk, max_active_k=spec.max_active_k,
            ) * gn_own.astype(jnp.float32)
        overflow = ops.budget_overflow(
            counts, gk=gk, max_active_k=spec.max_active_k
        )
    elif path == "kernel":
        sel = ops.skip_sel(enc.block_mask)
        out = ops.reuse_matmul(
            enc.delta, w, cache["prev_out"], enc.block_mask,
            block_m=spec.block_m, block_n=spec.block_n,
            block_k=spec.block_k,
            dataflow=spec.dataflow,
            interpret=interpret, sel=sel, layer=layer,
        )
    else:
        raise ValueError(
            f"unknown exec_path {path!r} for site {spec.name!r}"
        )
    row_sim = row_code_similarity(enc.cur_q, cache["prev_q"])
    sim = jnp.mean(row_sim)
    new_cache = dict(
        cache,
        prev_q=enc.cur_q,
        prev_out=out,
        sim_ema=ema_update(cache["sim_ema"], row_sim, ema_decay),
        steps=cache["steps"] + 1,
    )
    if "ctrl" in cache:
        # Per-layer budget occupancy: EMA of the live-tile fraction this
        # evaluation — the signal the budget adapter reads per layer.
        live = jnp.mean(enc.block_mask.astype(jnp.float32))
        new_cache["ctrl"] = dict(
            cache["ctrl"],
            occupancy=ema_update(cache["ctrl"]["occupancy"], live, ema_decay),
        )
    if "sensor" in cache:
        if dma_issued is None:  # kernel/dense: masked full-grid semantics
            if shard is None:
                dma_issued = ops.weight_dma_tiles(
                    enc.block_mask, gn=gn, dataflow=spec.dataflow, sel=sel,
                )
            else:
                dma_issued = ops.weight_dma_tiles(
                    enc.block_mask, gn=1, dataflow=spec.dataflow, sel=sel,
                ) * gn_own
        if grid_steps is None and shard is not None:
            # masked full-grid walk over the shard's owned global panels
            grid_steps = (jnp.int32(gm * gk) * gn_own).astype(jnp.float32)
        new_cache["sensor"] = update_on_reuse(
            cache["sensor"], block_mask=enc.block_mask, row_sim=row_sim,
            block_m=spec.block_m, block_k=spec.block_k, n=n, gn=gn,
            w_itemsize=w.dtype.itemsize,
            dma_issued=dma_issued,
            grid_steps=grid_steps,
            overflow=overflow,
            shard=shard,
        )
    stats = ReuseStats(
        similarity=sim,
        skip_fraction=enc.skip_fraction.astype(jnp.float32),
    )
    return out, new_cache, stats


def reuse_linear(
    x: jax.Array,                       # [..., K]
    w: jax.Array,                       # [K, N], or [L, K, N] with `layer`
    b: jax.Array | None,
    cache: dict[str, jax.Array],
    spec: ReuseSiteSpec,
    *,
    mode: str | None = "reuse",         # "reuse" | "basic" | None (= ctrl)
    impl: str = "jnp",
    ema_decay: float = 0.9,
    shard: ShardCtx | None = None,      # model-axis shard accounting context
    layer: jax.Array | None = None,     # int32 scalar index into a w stack
) -> tuple[jax.Array, dict[str, jax.Array], ReuseStats]:
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    xm = x.reshape(-1, k)
    m = xm.shape[0]
    assert cache["prev_q"].shape == (m, k), (cache["prev_q"].shape, (m, k))

    if mode == "basic":
        out, new_cache, stats = _basic_eval(xm, w, cache, spec, ema_decay,
                                            shard, layer)
    elif mode == "reuse":
        out, new_cache, stats = _reuse_eval(xm, w, cache, spec, impl,
                                            ema_decay, shard, layer)
    elif mode is None:
        # Array-resident kernelMode: branch on this layer's ctrl lane. Both
        # branches trace once (identical cache/stats structure); at runtime
        # the HLO conditional executes exactly one — so a host-side per-layer
        # flip between steps changes which branch runs without retracing.
        ctrl = cache.get("ctrl")
        if ctrl is None:
            raise ValueError(
                f"site {spec.name!r}: mode=None needs a ctrl block in the "
                "cache entry (engine.init_cache creates it)"
            )
        out, new_cache, stats = jax.lax.cond(
            ctrl["mode_id"] > 0,
            lambda: _reuse_eval(xm, w, cache, spec, impl, ema_decay, shard,
                                layer),
            lambda: _basic_eval(xm, w, cache, spec, ema_decay, shard, layer),
        )
    else:
        raise ValueError(f"unknown mode {mode!r}")

    if b is not None:
        out = out + b.astype(out.dtype)
    return out.astype(x.dtype).reshape(*lead, n), new_cache, stats
