"""The shared pieces of the plain float32 reference.

Straightforward `jax.numpy` at HIGHEST matmul precision, one request at a
time, with no KV cache, no reuse cache, no batching and no kernels. It
imports nothing of the program. An architecture module (`arch/`) builds its
layers from these pieces: RMSNorm with its weight stored as the offset from
1 (see weights.py), rotate-half RoPE, the rounding of a reuse site's input
to codes, and the float32 matmul. The output head is shared by every
architecture, and is read in vocabulary blocks.

`quant` = (bits, clip, start) rounds the input of a linear site to signed
`bits`-bit codes over [-clip, clip], at the positions from `start` on. With
the configuration's stated site codes from the first decode-fed position,
that is the served numerics of a reuse engine, whose output equals the dense
GEMM of the rounded input; one step down, it is the lower-precision control.
The weights stay resident in their served type, each layer is widened to
float32 only while it runs, and the head is read in blocks, so the reference
fits beside the served weights on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 16384


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv       # [T, 1, hd/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def round_codes(x, quant):
    """Round each row at or after position `start` to signed `bits`-bit
    codes over [-clip, clip]; rows before it stay as they are."""
    if quant is None:
        return x
    bits, clip, start = quant
    qmax = 2 ** (bits - 1) - 1
    step = clip / qmax
    q = jnp.clip(jnp.round(x / step), -qmax, qmax) * step
    rows = jnp.arange(x.shape[0]) >= start
    return jnp.where(rows.reshape(-1, *([1] * (x.ndim - 1))), q, x)


def mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HI)


def head(params):
    """The output head and whether it is stored [V, d]: `lm_head` [d, V],
    else the tied embedding."""
    if "lm_head" in params:
        return params["lm_head"], False
    return params["embed"], True


def vocab_size(params) -> int:
    w, rows = head(params)
    return w.shape[0] if rows else w.shape[1]


@functools.partial(jax.jit, static_argnames=("rows",))
def _max_argmax(h, w, *, rows):
    vocab = w.shape[0] if rows else w.shape[1]
    blk = min(VOCAB_BLOCK, vocab)
    n = -(-vocab // blk)

    def body(i, carry):
        best, arg = carry
        start = jnp.minimum(i * blk, vocab - blk)   # last block overlaps
        if rows:
            wb = jax.lax.dynamic_slice_in_dim(w, start, blk, 0).T
        else:
            wb = jax.lax.dynamic_slice_in_dim(w, start, blk, 1)
        logits = mm(h, wb)
        bmax = jnp.max(logits, axis=-1)
        barg = jnp.argmax(logits, axis=-1).astype(jnp.int32) + start
        take = bmax > best
        return jnp.where(take, bmax, best), jnp.where(take, barg, arg)

    init = (jnp.full(h.shape[:1], -jnp.inf, jnp.float32),
            jnp.zeros(h.shape[:1], jnp.int32))
    return jax.lax.fori_loop(0, n, body, init)


@functools.partial(jax.jit, static_argnames=("rows",))
def _logits_at(h, w, tokens, *, rows):
    cols = w[tokens].astype(jnp.float32) if rows else \
        w[:, tokens].T.astype(jnp.float32)
    return jnp.einsum("td,td->t", h, cols, precision=HI)


@functools.partial(jax.jit, static_argnames=("rows", "blk"))
def _count_above(h, w, tokens, got, *, rows, blk):
    vocab = w.shape[0] if rows else w.shape[1]
    n = -(-vocab // blk)

    def body(i, count):
        start = jnp.minimum(i * blk, vocab - blk)   # last block overlaps
        if rows:
            wb = jax.lax.dynamic_slice_in_dim(w, start, blk, 0).T
        else:
            wb = jax.lax.dynamic_slice_in_dim(w, start, blk, 1)
        ids = start + jnp.arange(blk)
        # each id once, and never the token itself (its logit here may
        # differ from `got` by the order of summation)
        keep = (ids >= i * blk)[None, :] & (ids[None, :] != tokens[:, None])
        above = (mm(h, wb) > got[:, None]) & keep
        return count + jnp.sum(above, axis=-1, dtype=jnp.int32)

    return jax.lax.fori_loop(0, n, body, jnp.zeros(h.shape[:1], jnp.int32))


def head_max_argmax(params, h):
    """Per position: the largest logit and its token."""
    w, rows = head(params)
    return _max_argmax(h, w, rows=rows)


def head_logits_at(params, h, tokens):
    """Per position t: the logit of tokens[t]."""
    w, rows = head(params)
    return _logits_at(h, w, jnp.asarray(tokens, jnp.int32), rows=rows)


def head_count_above(params, h, tokens, got):
    """Per position t: how many other tokens' logits lie above got[t], the
    logit of tokens[t] (0 where tokens[t] is the greedy choice)."""
    w, rows = head(params)
    return _count_above(h, w, jnp.asarray(tokens, jnp.int32), got,
                        rows=rows, blk=min(VOCAB_BLOCK, vocab_size(params)))
