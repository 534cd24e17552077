"""Public kernel API: padding, batch flattening, path dispatch.

Execution paths (per DESIGN.md §2; `ReuseSiteSpec.exec_path` selects one):
  "kernel"  — block-skip GEMM on the FULL (gm, gn, gk) grid: skipped tiles
              suppress the weight DMA and the MXU op but still cost a grid
              step. Compiled Pallas on TPU; the compiled-XLA masked lowering
              (kernels/xla_tier.py) where no Pallas lowering exists.
  "ragged"  — compacted-grid GEMM: the grid k-extent is a static budget
              `max_active_k` < gk; front-compacted indices walk only the
              ACTIVE tiles, so skipped tiles cost zero grid steps. Compiled
              Pallas scalar-prefetch on TPU; a `jnp.take` gather GEMM on the
              compiled-XLA tier. Runtime falls back to the full extent when a
              row's live count overflows the budget (correctness never
              depends on the policy's guess).
  "compact" — gather the nonzero K-blocks of Δ and the matching W row-blocks,
              dense GEMM on the compacted operands (MegaBlocks-style;
              beyond-paper). Pure jnp, shardable under pjit, and the path the
              CPU wall-clock benchmarks measure. With a static `max_blocks`
              budget the GEMM shape shrinks (same overflow fallback).
  "masked"  — branchless jnp.where software reuse (the paper's Sec.-III
              negative result: costs MORE than dense — kept as a benchmark).
  "dense"   — O_p-free ordinary GEMM (the "basic kernel" / reuse-OFF mode).
  "ref"     — oracle (tests only).

Substrate resolution (kernels/backend.py): every wrapper's `interpret`
parameter defaults to None = "best compiled substrate for this process",
resolved ONCE per process. `interpret=True` is the EXPLICIT interpret-mode
test path; `interpret=False` demands compiled Pallas and raises where none
exists. The old divergent defaults (ops.py said True, the kernel modules said
False) are gone — callers thread one explicit value or accept the resolved
compiled default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.delta import compact_block_indices, compact_rows
from repro.kernels import backend as _backend
from repro.kernels import ref as _ref
from repro.kernels import xla_tier as _xla
from repro.kernels.delta_quant import delta_quant as delta_quant_kernel
from repro.kernels.reuse_matmul import reuse_matmul as _reuse_matmul_kernel
from repro.kernels.reuse_matmul import skip_sel, weight_dma_tiles
from repro.kernels.reuse_matmul_int8 import reuse_matmul_int8 as _reuse_matmul_int8
from repro.kernels.reuse_matmul_ragged import (
    reuse_matmul_ragged as _reuse_matmul_ragged_kernel,
)

__all__ = [
    "reuse_matmul",
    "reuse_matmul_ragged",
    "reuse_matmul_compact",
    "reuse_matmul_masked",
    "delta_quant_fused",
    "reuse_matmul_int8",
    "weight_dma_tiles",
    "ragged_dma_tiles",
    "ragged_grid_steps",
    "budget_overflow",
    "clamp_budget",
    "skip_sel",
    "compact_rows",
]


def clamp_budget(max_active_k: int | None, gk: int) -> int:
    """Static k-extent budget, clamped to [1, gk]. ONE definition shared by
    the executing wrappers and the grid-step accounting — the sensor's
    grid_steps counter is only honest while both see the same extent."""
    if max_active_k is None:
        return gk
    return max(1, min(int(max_active_k), gk))


def layer_weight(w: jax.Array, layer: jax.Array | None) -> jax.Array:
    """One layer's `[K, N]` weight: `w` itself, or the `layer`-th slice of a
    `[L, K, N]` stack."""
    if layer is None:
        return w
    return jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    """Pad the last two dims to tile multiples; an aligned `x` (a layer stack
    included) is returned as it is, never copied."""
    p0 = (-x.shape[-2]) % mult0
    p1 = (-x.shape[-1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, p0), (0, p1)))
    return x


def reuse_matmul(
    delta: jax.Array,
    w: jax.Array,
    prev_out: jax.Array,
    block_mask: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    dataflow: str = "output",
    interpret: bool | None = None,
    sel: jax.Array | None = None,
    layer: jax.Array | None = None,
) -> jax.Array:
    """Padded/validated entry to the block-skip GEMM (masked full grid).

    `w` is `[K, N]`, or a layer stack `[L, K, N]` read at the int32 scalar
    `layer`: the Pallas kernel addresses a tile-aligned stack's tiles in
    place. The XLA tier, and a stack that would need padding, take the
    layer's slice, so the padding copies one layer and not the stack."""
    sub = _backend.resolve(interpret)
    if not (sub.use_pallas and w.shape[-2] % block_k == 0
            and w.shape[-1] % block_n == 0):
        w, layer = layer_weight(w, layer), None
    m, n = prev_out.shape
    dp = _pad_to(delta, block_m, block_k)
    wp = _pad_to(w, block_k, block_n)
    pp = _pad_to(prev_out.astype(jnp.float32), block_m, block_n)
    gm, gk = dp.shape[0] // block_m, dp.shape[1] // block_k
    assert block_mask.shape == (gm, gk), (block_mask.shape, (gm, gk))
    if sub.use_pallas:
        out = _reuse_matmul_kernel(
            dp, wp, pp, block_mask,
            block_m=block_m, block_n=block_n, block_k=block_k,
            dataflow=dataflow, interpret=sub.interpret, sel=sel, layer=layer,
        )
    else:
        out = _xla.reuse_matmul_xla(
            dp, wp, pp, block_mask, block_m=block_m, block_k=block_k,
        )
    return out[:m, :n]


def reuse_matmul_int8(
    delta_q: jax.Array,
    w_q: jax.Array,
    prev_acc: jax.Array,
    block_mask: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    sub = _backend.resolve(interpret)
    m, n = prev_acc.shape
    dp = _pad_to(delta_q, block_m, block_k)
    wp = _pad_to(w_q, block_k, block_n)
    pp = _pad_to(prev_acc, block_m, block_n)
    if sub.use_pallas:
        out = _reuse_matmul_int8(
            dp, wp, pp, block_mask,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=sub.interpret,
        )
    else:
        out = _xla.reuse_matmul_int8_xla(
            dp, wp, pp, block_mask, block_m=block_m, block_k=block_k,
        )
    return out[:m, :n]


def reuse_matmul_ragged(
    delta: jax.Array,       # [M, K]
    w: jax.Array,           # [K, N]
    prev_out: jax.Array,    # [M, N]
    block_mask: jax.Array,  # [gm, gk] int32; 1 = compute tile
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    max_active_k: int | None = None,
    interpret: bool | None = None,
    compacted: tuple[jax.Array, jax.Array] | None = None,  # (idx, counts)
) -> jax.Array:
    """Padded entry to the ragged compacted-grid GEMM.

    `max_active_k` is the static k-extent budget (None = gk, i.e. no grid
    shrink but still compaction-ordered). When any row's live tile count
    overflows the budget, a `lax.cond` falls back to the full-extent grid —
    the budget is a performance hint from the policy, never a correctness
    contract. `compacted` lets the caller thread a precomputed
    `compact_rows(block_mask)` (reuse_linear shares it with the accounting).
    On the compiled-XLA substrate the compacted walk runs as the gather GEMM
    (xla_tier.reuse_matmul_ragged_xla) with the same budget/fallback shape.
    """
    sub = _backend.resolve(interpret)
    m, n = prev_out.shape
    dp = _pad_to(delta, block_m, block_k)
    wp = _pad_to(w, block_k, block_n)
    pp = _pad_to(prev_out.astype(jnp.float32), block_m, block_n)
    gm, gk = dp.shape[0] // block_m, dp.shape[1] // block_k
    assert block_mask.shape == (gm, gk), (block_mask.shape, (gm, gk))
    if compacted is None:
        idx, counts = compact_rows(block_mask)
    else:
        idx, counts = compacted
    kb = clamp_budget(max_active_k, gk)

    def run(n_k: int) -> jax.Array:
        if sub.use_pallas:
            return _reuse_matmul_ragged_kernel(
                dp, wp, pp, counts, idx[:, :n_k],
                block_m=block_m, block_n=block_n, block_k=block_k,
                interpret=sub.interpret,
            )
        return _xla.reuse_matmul_ragged_xla(
            dp, wp, pp, counts, idx[:, :n_k],
            block_m=block_m, block_n=block_n, block_k=block_k,
        )

    if kb >= gk:
        out = run(gk)
    else:
        out = jax.lax.cond(
            jnp.any(counts > kb), lambda: run(gk), lambda: run(kb)
        )
    return out[:m, :n]


def ragged_dma_tiles(counts: jax.Array, *, gn: int) -> jax.Array:
    """Measured weight-tile DMA count under the ragged kernel's semantics.

    Per (m, n) output panel the weight index walks the row's `count` active
    blocks (the compacted tail repeats the last id — no new copy); a
    fully-skipped row still holds one resident tile. Same (block_k × block_n)
    tile units as `weight_dma_tiles`.
    """
    return (jnp.sum(jnp.maximum(counts, 1)) * gn).astype(jnp.int32)


def ragged_grid_steps(
    counts: jax.Array, *, gm: int, gn: int, gk: int, max_active_k: int | None
) -> jax.Array:
    """Grid steps the ragged path actually executes (fallback-aware).

    The compacted grid runs gm·gn·kb steps; when any row overflows the budget
    the wrapper re-runs the full gm·gn·gk extent, and the accounting must say
    so — saved steps are counted like saved DMAs: only when truly elided.
    """
    kb = clamp_budget(max_active_k, gk)
    if kb >= gk:
        return jnp.asarray(gm * gn * gk, jnp.float32)
    return jnp.where(
        jnp.any(counts > kb), float(gm * gn * gk), float(gm * gn * kb)
    )


def budget_overflow(
    counts: jax.Array, *, gk: int, max_active_k: int | None
) -> jax.Array:
    """1 when an evaluation's live tile counts overflow the static budget —
    i.e. the compacted wrappers' `lax.cond` took the full-extent fallback —
    else 0. `counts` is the ragged per-row count vector or the compact path's
    scalar live-block count. Shares `clamp_budget` with the executing
    wrappers, so the sensor's `overflow_fallbacks` counter can only disagree
    with the branch actually taken if the wrappers themselves change."""
    kb = clamp_budget(max_active_k, gk)
    if kb >= gk:
        return jnp.zeros((), jnp.int32)
    return jnp.any(counts > kb).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_k", "max_blocks"))
def _compact_gemm(
    delta: jax.Array,
    w: jax.Array,
    prev_out: jax.Array,
    k_block_mask: jax.Array,
    *,
    block_k: int,
    max_blocks: int,
) -> jax.Array:
    mrows, k = delta.shape
    gk = k // block_k
    idx, count = compact_block_indices(k_block_mask)
    nb = max_blocks
    idx = idx[:nb]
    # Zero-weight blocks beyond `count` so the tail contributes nothing even
    # when it aliases a real block.
    valid = (jnp.arange(nb) < count).astype(delta.dtype)
    d_blocks = delta.reshape(mrows, gk, block_k).transpose(1, 0, 2)[idx]
    d_blocks = d_blocks * valid[:, None, None]
    w_blocks = w.reshape(gk, block_k, -1)[idx]
    # [nb, M, bk] × [nb, bk, N] — contract over (blocks, bk) at once.
    upd = jnp.einsum(
        "gmk,gkn->mn", d_blocks, w_blocks,
        preferred_element_type=jnp.float32,
    )
    return prev_out + upd


def reuse_matmul_compact(
    delta: jax.Array,       # [M, K]
    w: jax.Array,           # [K, N]
    prev_out: jax.Array,    # [M, N]
    k_block_mask: jax.Array,  # [gk] int32 — per-K-block "any row changed"
    *,
    block_k: int = 256,
    max_blocks: int | None = None,
) -> jax.Array:
    """Compaction path: gather nonzero K-blocks of Δ and W, dense GEMM.

    Shared-K masking (one mask bit per K-block across all rows) keeps the
    gather a clean 2-D slice gather that GSPMD shards on the N axis. With
    `max_blocks` static (< gk) the GEMM shape shrinks — the policy's
    compacted budget on CPU serving; a `lax.cond` falls back to the full
    extent whenever the live block count overflows the budget. K is padded
    to a block_k multiple (padding blocks carry zero deltas and an inactive
    mask bit, so they are never gathered).
    """
    kp = (-delta.shape[1]) % block_k
    if kp:
        # The caller's mask is already on the ceil(K/block_k) grid
        # (block_zero_mask pads virtually); only the operands need real pads.
        delta = jnp.pad(delta, ((0, 0), (0, kp)))
        w = jnp.pad(w, ((0, kp), (0, 0)))
    gk = delta.shape[1] // block_k
    assert k_block_mask.shape == (gk,), (k_block_mask.shape, gk)
    prev_out = prev_out.astype(jnp.float32)
    nb = clamp_budget(max_blocks, gk)

    def run(n_blocks: int) -> jax.Array:
        return _compact_gemm(delta, w, prev_out, k_block_mask,
                             block_k=block_k, max_blocks=n_blocks)

    if nb >= gk:
        return run(gk)
    count = jnp.sum((k_block_mask != 0).astype(jnp.int32))
    return jax.lax.cond(count > nb, lambda: run(gk), lambda: run(nb))


def reuse_matmul_masked(
    delta: jax.Array, w: jax.Array, prev_out: jax.Array
) -> jax.Array:
    """Software reuse, branchless: the Sec.-III negative result on TPU.

    Masks deltas with `where` but still issues the full GEMM — all the delta
    bookkeeping, none of the skipping. Benchmarked to show it is *slower*
    than the dense baseline, reproducing the paper's motivation.
    """
    d = jnp.where(delta != 0, delta, jnp.zeros_like(delta))
    return prev_out + jnp.dot(d, w, preferred_element_type=jnp.float32)


def delta_quant_fused(
    x: jax.Array,
    prev_q: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_k: int = 256,
    delta_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Padded entry to the fused delta/quant/mask pass."""
    sub = _backend.resolve(interpret)
    m, k = x.shape
    xp = _pad_to(x, block_m, block_k)
    pq = _pad_to(prev_q, block_m, block_k)
    if sub.use_pallas:
        q, delta, mask = delta_quant_kernel(
            xp, pq, scale, block_m=block_m, block_k=block_k,
            delta_dtype=delta_dtype, interpret=sub.interpret,
        )
    else:
        q, delta, mask = _xla.delta_quant_xla(
            xp, pq, scale, block_m=block_m, block_k=block_k,
            delta_dtype=delta_dtype,
        )
    return q[:m, :k], delta[:m, :k], mask


# Re-exported oracles so tests import one module.
reuse_matmul_ref = _ref.reuse_matmul_ref
reuse_matmul_int8_ref = _ref.reuse_matmul_int8_ref
delta_quant_ref = _ref.delta_quant_ref
