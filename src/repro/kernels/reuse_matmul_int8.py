"""Int8 block-skip ΔW GEMM — the `mla8` analogue (paper Sec. IV-A).

The paper extends ARM SVE `mla` to `mla8`: 8-bit multiplies accumulated into
32-bit destinations so quantized DNNs can exploit per-element skipping without
overflow. The MXU equivalent is an int8 × int8 → int32 matmul tile; overflow
of the *delta itself* (|q_c − q_p| > 127) is handled by the caller via the
paper's split trick (core.delta.delta_encode_int8) — the `hi` component is
routed through this same kernel and its near-empty mask makes it nearly free.

Structure mirrors reuse_matmul.py (output-stationary): scalar-prefetched `sel`
suppresses weight-tile DMAs, @pl.when suppresses MXU ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.reuse_matmul import _skip_sel


def _kernel(mask_ref, sel_ref, delta_ref, w_ref, prev_ref, out_ref, acc_ref, *, n_k: int):
    m = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = prev_ref[...]

    @pl.when(mask_ref[m, k] != 0)
    def _compute():
        acc_ref[...] += jnp.dot(
            delta_ref[...].astype(jnp.int32),
            w_ref[...].astype(jnp.int32),
            preferred_element_type=jnp.int32,
        )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def reuse_matmul_int8(
    delta_q: jax.Array,     # [M, K] int8 (lo or hi component)
    w_q: jax.Array,         # [K, N] int8
    prev_acc: jax.Array,    # [M, N] int32
    block_mask: jax.Array,  # [gm, gk] int32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    m, k = delta_q.shape
    _, n = w_q.shape
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    gm, gk, gn = m // block_m, k // block_k, n // block_n
    sel = _skip_sel(block_mask)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, msk, sl: (mi, sl[mi, ki])),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki, msk, sl: (sl[mi, ki], ni)),
            pl.BlockSpec((block_m, block_n), lambda mi, ni, ki, msk, sl: (mi, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki, msk, sl: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=gk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(block_mask, sel, delta_q, w_q, prev_acc)
