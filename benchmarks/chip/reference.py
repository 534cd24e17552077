"""The plain float32 reference of a dense decoder, from its configuration.

Straightforward `jax.numpy` at HIGHEST matmul precision, one request at a
time and one layer at a time, with no KV cache, no reuse cache, no batching
and no kernels. It imports nothing of the program. It follows the published
layer equations of the configuration's `model` group:

    h   = x + Wo . attn(rope(norm_qk(q)), rope(norm_qk(k)), v),
          [q | k | v] = norm(x) . Wqkv        (grouped-query, causal)
    out = h + Wdown . act(norm(h) . Wup)      (silu(gate) * up, or relu^2)
    logits = norm(out_L) . Whead              (Whead = embed^T when tied)

with RMSNorm (weight stored as its offset from 1, see weights.py) and
rotate-half RoPE over the whole head.

`quant` = (bits, clip, start) rounds the input of each of the four linear
sites of every layer to signed `bits`-bit codes over [-clip, clip], at the
positions from `start` on. With the configuration's stated site codes from
the first decode-fed position, that is the served numerics of a reuse
engine, whose output equals the dense GEMM of the rounded input; one step
down, it is the lower-precision control.
The weights stay resident in their served type; each layer is widened to
float32 only while it runs, and the output head is read in vocabulary
blocks, so the reference fits beside the served weights on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 16384


def spec_of(model: dict) -> tuple:
    """The hashable sizes the reference needs, from a `model` group."""
    return (
        model["hidden_size"], model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"],
        model["hidden_act"], bool(model["qk_norm"]),
        float(model["rope_theta"]), float(model["rms_norm_eps"]),
        bool(model["tie_word_embeddings"]), model["vocab_size"],
    )


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv       # [T, 1, hd/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _codes(x, quant):
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


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HI)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _layer(x, blocks, layer, *, spec, quant):
    d, nh, nkv, hd, act, qk_norm, theta, eps, _, _ = spec
    w = jax.tree.map(lambda a: a[layer], blocks)
    t = x.shape[0]
    pos = jnp.arange(t)

    a = w["attn"]
    qkv = _mm(_codes(_norm(x, a["norm"]["scale"], eps), quant), a["wqkv"])
    q, k, v = jnp.split(qkv, [nh * hd, nh * hd + nkv * hd], axis=-1)
    q = q.reshape(t, nh, hd)
    k = k.reshape(t, nkv, hd)
    v = v.reshape(t, nkv, hd)
    if qk_norm:
        q = _norm(q, a["q_norm"]["scale"], eps)
        k = _norm(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = nh // nkv
    qg = q.reshape(t, nkv, rep, hd)     # query head h reads kv head h // rep
    s = jnp.einsum("qgrd,kgd->grqk", qg, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * hd)
    x = x + _mm(_codes(o, quant), a["wo"])

    m = w["mlp"]
    hi = _mm(_codes(_norm(x, m["norm"]["scale"], eps), quant), m["wi"])
    if act == "silu":
        gate, up = jnp.split(hi, 2, axis=-1)
        hi = jax.nn.silu(gate) * up
    elif act == "relu2":
        hi = jnp.square(jnp.maximum(hi, 0.0))
    else:
        raise ValueError(f"unknown hidden_act {act!r}")
    return x + _mm(_codes(hi, quant), m["wo"])


@functools.partial(jax.jit, static_argnames=("spec",))
def _final(x, scale, *, spec):
    return _norm(x, scale, spec[7])


def final_hidden(params, spec, tokens, quant=None) -> jax.Array:
    """Normalised last hidden state [T, d] in float32 for one request."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for layer in range(params["blocks"]["attn"]["wqkv"].shape[0]):
        x = _layer(x, params["blocks"], layer, spec=spec, quant=quant)
    return _final(x, params["final_norm"]["scale"], spec=spec)


def _head(params, spec):
    """The output head and whether it is stored [V, d] (tied embedding)."""
    if spec[8]:
        return params["embed"], True
    return params["lm_head"], False


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
        logits = _mm(h, wb)
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
        above = (_mm(h, wb) > got[:, None]) & keep
        return count + jnp.sum(above, axis=-1, dtype=jnp.int32)

    return jax.lax.fori_loop(0, n, body, jnp.zeros(h.shape[:1], jnp.int32))


def head_max_argmax(params, spec, h):
    """Per position: the largest logit and its token."""
    w, rows = _head(params, spec)
    return _max_argmax(h, w, rows=rows)


def head_logits_at(params, spec, h, tokens):
    """Per position t: the logit of tokens[t]."""
    w, rows = _head(params, spec)
    return _logits_at(h, w, jnp.asarray(tokens, jnp.int32), rows=rows)


def head_count_above(params, spec, h, tokens, got):
    """Per position t: how many other tokens' logits lie above got[t], the
    logit of tokens[t] (0 where tokens[t] is the greedy choice)."""
    w, rows = _head(params, spec)
    return _count_above(h, w, jnp.asarray(tokens, jnp.int32), got,
                        rows=rows, blk=min(VOCAB_BLOCK, spec[9]))
