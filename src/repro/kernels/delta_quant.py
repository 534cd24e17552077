"""Fused quantize + delta + tile-mask kernel (the delta-value-register analogue).

The paper's ReuseSensor computes deltas with generated `sub` instructions and
copies the result into an in-unit delta-value register that the generation
logic consults. On TPU the equivalent hot loop is a single memory-bound pass:

    read x (current activations, bf16/f32) and prev_q (int8 codes)
    -> cur_q = quantize(x)            (int8 codes, written back to the cache)
    -> delta = scale * (cur_q - prev_q)   (exact-zero where codes match)
    -> mask[m, k] = any(delta_tile != 0)  (one bit per (block_m × block_k) tile)

Fusing the three avoids two extra HBM round-trips of the activation tensor —
this is a beyond-paper optimization (the paper's engine gets it for free in
hardware; we must claim it explicitly).

The mask output is the whole [gm, gk] int32 array as ONE SMEM block (a block
equal to the full array is the only SMEM shape the TPU lowering accepts for a
tile count that is not a multiple of (8, 128)); each grid step writes its own
element at (program_id(0), program_id(1)). Because every step revisits that
one output block, both grid axes are "arbitrary" (sequential) — splitting
them across cores would race on the shared block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(scale_ref, x_ref, prev_q_ref, q_ref, delta_ref, mask_ref):
    scale = scale_ref[0]
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / scale), -127, 127)
    dq = q.astype(jnp.int32) - prev_q_ref[...].astype(jnp.int32)
    q_ref[...] = q.astype(jnp.int8)
    delta_ref[...] = (dq.astype(jnp.float32) * scale).astype(delta_ref.dtype)
    mask_ref[pl.program_id(0), pl.program_id(1)] = jnp.any(dq != 0).astype(
        jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "delta_dtype", "interpret")
)
def delta_quant(
    x: jax.Array,        # [M, K] float
    prev_q: jax.Array,   # [M, K] int8
    scale: jax.Array,    # scalar f32
    *,
    block_m: int = 128,
    block_k: int = 256,
    delta_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (cur_q int8 [M,K], delta [M,K] in delta_dtype, mask int32
    [gm,gk]). `delta_dtype` follows the weight dtype of the consuming GEMM:
    f32 weights need an f32 delta to keep the telescoping invariant exact."""
    m, k = x.shape
    assert m % block_m == 0 and k % block_k == 0, (x.shape, block_m, block_k)
    gm, gk = m // block_m, k // block_k
    scale_arr = jnp.reshape(scale.astype(jnp.float32), (1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # scale
        grid=(gm, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ki, s: (mi, ki)),
            pl.BlockSpec((block_m, block_k), lambda mi, ki, s: (mi, ki)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ki, s: (mi, ki)),
            pl.BlockSpec((block_m, block_k), lambda mi, ki, s: (mi, ki)),
            pl.BlockSpec(
                (gm, gk), lambda mi, ki, s: (0, 0), memory_space=pltpu.SMEM
            ),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, k), delta_dtype),
            jax.ShapeDtypeStruct((gm, gk), jnp.int32),
        ],
        interpret=interpret,
        name="delta_quant",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(scale_arr, x, prev_q)
