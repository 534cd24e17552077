"""Fused WKV6 decode-step kernel — the rwkv6 long_500k serving hot-spot.

One autoregressive RWKV6 step per head is four elementwise passes over the
[dk, dv] state in naive jnp (outer product, bonus-add, readout, decay-update)
— memory-bound on the state, which at 4 reads+writes dominates the rwkv6
long-decode memory term. This kernel fuses the whole step into ONE
HBM→VMEM→HBM pass over the state:

    kv   = kᵀ v                       (outer product, in VMEM)
    out  = r · (diag(u)·kv + S)       (readout)
    S'   = diag(w)·S + kv             (decay update, written in place)

Grid: one program per (batch·head); the [dk, dv] state tile lives in VMEM.
Validated in interpret mode against the pure-jnp oracle (= the step body of
models/ssm.rwkv6_time_mix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref, out_ref, s_new_ref):
    r = r_ref[0].astype(jnp.float32)        # [dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)        # [dv]
    w = w_ref[0].astype(jnp.float32)        # [dk]
    u = u_ref[0].astype(jnp.float32)        # [dk]
    s = s_ref[0].astype(jnp.float32)        # [dk, dv]

    kv = k[:, None] * v[None, :]            # [dk, dv]
    out = jnp.sum(r[:, None] * (u[:, None] * kv + s), axis=0)   # [dv]
    out_ref[0] = out.astype(out_ref.dtype)
    s_new_ref[0] = (w[:, None] * s + kv).astype(s_new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_decode(
    r: jax.Array,   # [B, H, dk]
    k: jax.Array,   # [B, H, dk]
    v: jax.Array,   # [B, H, dv]
    w: jax.Array,   # [B, H, dk]   per-channel decay in (0, 1)
    u: jax.Array,   # [H, dk]      bonus
    state: jax.Array,  # [B, H, dk, dv] f32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [B, H, dv], new_state [B, H, dk, dv])."""
    b, h, dk = r.shape
    dv = v.shape[-1]
    bh = b * h

    rf = r.reshape(bh, dk)
    kf = k.reshape(bh, dk)
    vf = v.reshape(bh, dv)
    wf = w.reshape(bh, dk)
    uf = jnp.broadcast_to(u[None], (b, h, dk)).reshape(bh, dk)
    sf = state.reshape(bh, dk, dv)

    vec = pl.BlockSpec((1, dk), lambda i: (i, 0))
    vecv = pl.BlockSpec((1, dv), lambda i: (i, 0))
    mat = pl.BlockSpec((1, dk, dv), lambda i: (i, 0, 0))

    out, s_new = pl.pallas_call(
        _kernel,
        grid=(bh,),
        in_specs=[vec, vec, vecv, vec, vec, mat],
        out_specs=[vecv, mat],
        out_shape=[
            jax.ShapeDtypeStruct((bh, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(rf, kf, vf, wf, uf, sf)
    return out.reshape(b, h, dv), s_new.reshape(b, h, dk, dv)


def wkv6_decode_ref(r, k, v, w, u, state):
    """Pure-jnp oracle (identical math to models/ssm.rwkv6_time_mix's step)."""
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    out = jnp.einsum("bhk,bhkv->bhv", rf,
                     u[None, :, :, None].astype(jnp.float32) * kv + state)
    s_new = wf[..., :, None] * state + kv
    return out, s_new
