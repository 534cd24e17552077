"""Block-skip ΔW GEMM — the TPU translation of the ReuseSensor (paper Sec. IV).

The paper's hardware unit walks the kernel of Fig. 7-B and, when a delta is
zero, *does not emit* the weight load or the `mla8` op. On a TPU the analogous
levers are (a) the HBM→VMEM DMA of a weight tile and (b) the MXU issue for that
tile. This kernel skips both:

* a scalar-prefetched `sel` table drives the weight/delta `BlockSpec`
  index_maps: for a skipped (m, k) tile, `sel[m, k]` repeats the previously
  loaded block index, so the Pallas pipeline emits **no new copy** — the DMA
  that would have streamed that weight tile simply never happens (the paper's
  "skipping weight loads");
* `@pl.when(mask[m, k] != 0)` suppresses the MXU dot for that tile (the
  paper's "bypassing computations").

Grid/dataflow:

* `output` stationary (default; what ARMNN's sdot kernels use, Fig. 5): grid
  (gm, gn, gk), k innermost; a VMEM scratch accumulator is initialized from
  `prev_out` at k = 0 and written back at k = gk − 1. Skipped k-steps touch
  neither HBM nor the MXU.
* `input` stationary (the paper's 3DUnet analysis): grid (gm, gk, gn), the
  delta tile is resident while n sweeps; the output block is read-modified-
  written via input/output aliasing. More output traffic when N is large —
  exactly the regression the paper reports for 3DUnet — measured in
  benchmarks/dataflow.py.

The weight may be one layer's `[K, N]` or a layer stack `[L, K, N]` with a
`layer` index. The index is one more scalar-prefetch operand and the weight
`BlockSpec` addresses `(layer, k, n)`, so a scan over layers hands the kernel
the stacked parameter itself and no per-layer copy of the weight is made.

Tile sizes default to MXU-aligned (block_k, block_n multiples of 128; block_m
multiples of 8). Correctness is validated in interpret mode against
`ref.reuse_matmul_ref` over shape/dtype/mask sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _skip_sel(block_mask: jax.Array) -> jax.Array:
    """sel[m, k] = index of the newest non-skipped k'-block with k' <= k.

    Repeating the previous index across skipped steps is what suppresses the
    DMA (Pallas only issues a copy when the block index changes). Cold prefix
    (no nonzero block yet) clamps to 0 — harmless: the compute is @pl.when-ed
    off, the tile is merely resident.
    """
    gm, gk = block_mask.shape
    ks = jnp.arange(gk, dtype=jnp.int32)[None, :]
    marked = jnp.where(block_mask != 0, ks, -1)
    sel = jax.lax.cummax(marked, axis=1)
    return jnp.maximum(sel, 0).astype(jnp.int32)


# Public alias: reuse_linear builds the table once per call and threads it
# into both the kernel launch and the DMA accounting.
skip_sel = _skip_sel


def weight_dma_tiles(
    block_mask: jax.Array,
    *,
    gn: int,
    dataflow: str = "output",
    sel: jax.Array | None = None,
) -> jax.Array:
    """Measured weight-tile DMA count under this kernel's sel semantics.

    The sensor subsystem's ground truth for "weight loads actually issued":
    Pallas emits a copy only when a BlockSpec index changes between grid
    steps, so the issue count is a property of the sel table, not of the
    mask alone (the cold prefix clamps to tile 0, which still costs one
    resident load per (m, n) panel).

    * output-stationary, grid (gm, gn, gk): per (m, n) panel the w index is
      (sel[m, k], n) — one load at k = 0 plus one per sel transition;
    * input-stationary, grid (gm, gk, gn): a computed (m, k) tile sweeps gn
      weight tiles; masked steps pin both coordinates (no copy issued).

    Cheap trace-side math on the [gm, gk] mask — used for accounting, never
    on the kernel's own critical path. When the caller already built the sel
    table for the kernel launch, pass it as `sel` to avoid recomputing it.
    """
    if sel is None:
        sel = _skip_sel(block_mask)
    if dataflow == "output":
        transitions = jnp.sum((sel[:, 1:] != sel[:, :-1]).astype(jnp.int32))
        rows = block_mask.shape[0]
        return (transitions + rows) * gn
    return jnp.sum((block_mask != 0).astype(jnp.int32)) * gn


def _kernel_output_stationary(
    mask_ref, sel_ref, layer_ref, delta_ref, w_ref, prev_ref, out_ref, acc_ref,
    *, n_k: int,
):
    m = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prev_ref[...].astype(jnp.float32)

    @pl.when(mask_ref[m, k] != 0)
    def _compute():
        acc_ref[...] += jnp.dot(
            delta_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _kernel_input_stationary(
    mask_ref, sel_ref, layer_ref, delta_ref, w_ref, prev_ref, out_ref, acc_ref,
    *, n_k: int, block_n: int,
):
    """Delta tile resident; the full output row-panel lives in VMEM scratch.

    Grid is (gm, gk, gn) — n innermost, so one delta tile serves gn weight
    tiles before moving on (input stationary). Output panel is initialized
    from prev_out during the k == 0 sweep and flushed on the last k sweep.
    """
    m = pl.program_id(0)
    k = pl.program_id(1)
    n = pl.program_id(2)
    nslice = pl.ds(n * block_n, block_n)

    @pl.when(k == 0)
    def _init():
        acc_ref[:, nslice] = prev_ref[...].astype(jnp.float32)

    @pl.when(mask_ref[m, k] != 0)
    def _compute():
        acc_ref[:, nslice] += jnp.dot(
            delta_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[:, nslice].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "dataflow", "interpret"),
)
def reuse_matmul(
    delta: jax.Array,       # [M, K] bf16/f32 — zero wherever codes matched
    w: jax.Array,           # [K, N], or a layer stack [L, K, N]
    prev_out: jax.Array,    # [M, N] f32
    block_mask: jax.Array,  # [gm, gk] int32 (gm = M/block_m, gk = K/block_k)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    dataflow: str = "output",
    interpret: bool = False,
    sel: jax.Array | None = None,  # precomputed _skip_sel(block_mask)
    layer: jax.Array | None = None,  # int32 scalar; required with a stack
) -> jax.Array:
    """O_c = O_p + Δ·W[layer], skipping weight-tile DMAs and MXU ops for zero
    tiles. A `[K, N]` weight is the one-layer stack."""
    if w.ndim == 2:
        assert layer is None, "a layer index needs a [L, K, N] stack"
        w, layer = w[None], 0
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    m, k = delta.shape
    k2, n = w.shape[1:]
    assert k == k2, (delta.shape, w.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        "caller (ops.reuse_linear_kernel) pads to tile multiples",
        (m, k, n),
        (block_m, block_k, block_n),
    )
    gm, gk, gn = m // block_m, k // block_k, n // block_n
    assert block_mask.shape == (gm, gk), (block_mask.shape, (gm, gk))

    if sel is None:
        sel = _skip_sel(block_mask)

    if dataflow == "output":
        grid = (gm, gn, gk)

        def delta_map(mi, ni, ki, mask, sel, layer):
            return (mi, sel[mi, ki])

        def w_map(mi, ni, ki, mask, sel, layer):
            return (layer[0], sel[mi, ki], ni)

        def prev_map(mi, ni, ki, mask, sel, layer):
            return (mi, ni)

        def out_map(mi, ni, ki, mask, sel, layer):
            return (mi, ni)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), delta_map),
                pl.BlockSpec((None, block_k, block_n), w_map),
                pl.BlockSpec((block_m, block_n), prev_map),
            ],
            out_specs=pl.BlockSpec((block_m, block_n), out_map),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        )
        kernel = functools.partial(_kernel_output_stationary, n_k=gk)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), prev_out.dtype),
            interpret=interpret,
            name="reuse_matmul_output",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
        )(block_mask, sel, layer, delta, w, prev_out)

    elif dataflow == "input":
        grid = (gm, gk, gn)

        def delta_map(mi, ki, ni, mask, sel, layer):
            return (mi, sel[mi, ki])

        def w_map(mi, ki, ni, mask, sel, layer):
            # Freeze BOTH coordinates across a fully-masked k sweep so no
            # weight DMA is issued for skipped tiles (n pinned to the last
            # block fetched before entering the masked region).
            return (layer[0], sel[mi, ki],
                    jnp.where(mask[mi, ki] != 0, ni, gn - 1))

        def prev_map(mi, ki, ni, mask, sel, layer):
            # prev_out is only consumed during the k == 0 sweep; freeze after.
            return (mi, jnp.where(ki == 0, ni, gn - 1))

        def out_map(mi, ki, ni, mask, sel, layer):
            return (mi, ni)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), delta_map),
                pl.BlockSpec((None, block_k, block_n), w_map),
                pl.BlockSpec((block_m, block_n), prev_map),
            ],
            out_specs=pl.BlockSpec((block_m, block_n), out_map),
            scratch_shapes=[pltpu.VMEM((block_m, n), jnp.float32)],
        )
        kernel = functools.partial(
            _kernel_input_stationary, n_k=gk, block_n=block_n
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), prev_out.dtype),
            interpret=interpret,
            name="reuse_matmul_input",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            ),
        )(block_mask, sel, layer, delta, w, prev_out)

    raise ValueError(f"unknown dataflow {dataflow!r}")
