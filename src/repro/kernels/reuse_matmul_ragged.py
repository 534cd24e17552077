"""Ragged compacted-grid ΔW GEMM — skipped tiles cost ZERO grid steps.

The masked kernel (reuse_matmul.py) suppresses the weight DMA and the MXU op
for a skipped (m, k) tile, but the grid still *visits* the tile: every skipped
step burns a full pipeline slot walking `sel`/`mask`. At an 83 % skip rate the
sensor shows almost none of that as step time — the paper's unit wins because
skipped dot products never issue at all.

This kernel makes the grid itself ragged: the k-extent is a static budget
`max_active_k` (chosen by the policy from the measured skip rate) instead of
`gk`. Per m-row-block, scalar-prefetched front-compacted block indices
(`compact_block_indices`) and a per-row active count drive the delta/weight
index_maps, so grid step k touches the k-th *active* block:

    delta block  -> (m, idx[m, k])
    weight block -> (idx[m, k], n)
    @pl.when(k < count[m]) guards the tail (idx repeats the last valid id
    there, so the resident tiles are never re-fetched and never computed).

A row with count == 0 passes prev_out straight through. Rows can have
*different* counts — the grid is sized for the budget, the guard trims each
row to its own raggedness. Correctness for counts that overflow the budget is
handled by the `ops.reuse_matmul_ragged` wrapper (runtime fallback to the
full-extent grid), not here: this kernel assumes count[m] <= n_k or accepts
that overflowing rows compute only their first n_k active blocks.

Output-stationary only (grid (gm, gn, kb), k innermost): the compaction is
per m-row, which is exactly the output-stationary iteration; an
input-stationary sweep would re-gather per n and win nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(count_ref, idx_ref, delta_ref, w_ref, prev_ref, out_ref, acc_ref,
            *, n_k: int):
    m = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prev_ref[...].astype(jnp.float32)

    @pl.when(k < count_ref[m])
    def _compute():
        acc_ref[...] += jnp.dot(
            delta_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def reuse_matmul_ragged(
    delta: jax.Array,       # [M, K] bf16/f32 — zero wherever codes matched
    w: jax.Array,           # [K, N]
    prev_out: jax.Array,    # [M, N] f32
    counts: jax.Array,      # [gm] int32 — active K-blocks per m-row-block
    idx: jax.Array,         # [gm, kb] int32 — front-compacted block indices
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """O_c = O_p + Δ·W over a compacted k-grid of extent kb = idx.shape[1]."""
    m, k = delta.shape
    k2, n = w.shape
    assert k == k2, (delta.shape, w.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        "caller (ops.reuse_matmul_ragged) pads to tile multiples",
        (m, k, n),
        (block_m, block_k, block_n),
    )
    gm, gn = m // block_m, n // block_n
    kb = idx.shape[1]
    assert 1 <= kb <= k // block_k, (kb, k // block_k)
    assert counts.shape == (gm,) and idx.shape == (gm, kb), (
        counts.shape, idx.shape, (gm, kb),
    )

    grid = (gm, gn, kb)

    def delta_map(mi, ni, ki, count, idx):
        return (mi, idx[mi, ki])

    def w_map(mi, ni, ki, count, idx):
        return (idx[mi, ki], ni)

    def prev_map(mi, ni, ki, count, idx):
        return (mi, ni)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), delta_map),
            pl.BlockSpec((block_k, block_n), w_map),
            pl.BlockSpec((block_m, block_n), prev_map),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), prev_map),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    kernel = functools.partial(_kernel, n_k=kb)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), prev_out.dtype),
        interpret=interpret,
        name="reuse_matmul_ragged",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(counts.astype(jnp.int32), idx.astype(jnp.int32), delta, w, prev_out)
