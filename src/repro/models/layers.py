"""Shared layer implementations: norms, RoPE (+M-RoPE), attention, MLP.

Pure-JAX (explicit param pytrees, no framework). Attention uses a *pair-scan*
blockwise formulation: the static list of (q-chunk, kv-chunk) pairs that the
mask admits is enumerated at trace time and scanned with an online-softmax
carry. This gives flash-attention memory behaviour AND exact mask-aware FLOPs
in the lowered HLO (no masked-out upper-triangle waste), which keeps the
roofline analysis honest. Causal, sliding-window and bidirectional patterns
only differ in their pair list.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------- init utils

def _dense_init(key, shape, scale=None, dtype=jnp.bfloat16):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------- norms

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def init_norm(d: int, kind: str = "rms") -> Params:
    if kind == "rms":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p["scale"], eps)


# ----------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    freqs = rope_freqs(x.shape[-1], theta)                     # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                         # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float, sections=(16, 24, 24)
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): the D/2 frequency slots are partitioned
    into (temporal, height, width) sections, each rotated by its own position
    stream. positions: [3, ..., S] (for text, all three streams coincide and
    M-RoPE degenerates to RoPE)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                # [D/2]
    assert sum(sections) == d // 2, (sections, d)
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=d // 2
    )
    # pick, per frequency slot, the position stream of its section
    pos = jnp.take(positions, sec_id, axis=0)                   # [D/2, ..., S]
    pos = jnp.moveaxis(pos, 0, -1)                              # [..., S, D/2]
    angles = pos.astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ attention

def init_attention(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Params = {
        "wqkv": _dense_init(ks[0], (d, cfg.q_dim + 2 * cfg.kv_dim), dtype=cfg.dtype),
        "wo": _dense_init(ks[1], (cfg.q_dim, d), dtype=cfg.dtype),
        "norm": init_norm(d),
    }
    if cfg.qkv_bias:
        p["bqkv"] = jnp.zeros((cfg.q_dim + 2 * cfg.kv_dim,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg.head_dim)
        p["k_norm"] = init_norm(cfg.head_dim)
    return p


def _split_qkv(cfg: ModelConfig, qkv: jax.Array):
    q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
    b, s = q.shape[:2]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _chunk_pairs(
    n_q: int, n_kv: int, chunk_q: int, chunk_kv: int, *,
    causal: bool, window: int | None, q_offset: int = 0,
) -> list[tuple[int, int]]:
    """Static (q-chunk, kv-chunk) pair list admitted by the mask."""
    pairs = []
    for i in range(n_q):
        q_lo = q_offset + i * chunk_q
        q_hi = q_lo + chunk_q - 1
        for j in range(n_kv):
            k_lo = j * chunk_kv
            k_hi = k_lo + chunk_kv - 1
            if causal and k_lo > q_hi:
                continue  # entirely in the future
            if window is not None and k_hi < q_lo - window + 1:
                continue  # entirely before the window
            pairs.append((i, j))
    return pairs


def blockwise_attention(
    q: jax.Array,   # [B, Sq, H, D]
    k: jax.Array,   # [B, Skv, KV, D]
    v: jax.Array,   # [B, Skv, KV, D]
    *,
    causal: bool,
    window: int | None = None,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Pair-scan flash attention (see module docstring)."""
    b, sq, h, d = q.shape
    skv, kv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = 1.0 / math.sqrt(d)
    chunk_q = min(chunk_q, sq)
    chunk_kv = min(chunk_kv, skv)
    while sq % chunk_q:
        chunk_q -= 1   # largest divisor <= requested (odd smoke shapes)
    while skv % chunk_kv:
        chunk_kv -= 1
    nq, nkv = sq // chunk_q, skv // chunk_kv

    pairs = _chunk_pairs(
        nq, nkv, chunk_q, chunk_kv, causal=causal, window=window, q_offset=q_offset
    )
    qi = jnp.asarray([p[0] for p in pairs], dtype=jnp.int32)
    kj = jnp.asarray([p[1] for p in pairs], dtype=jnp.int32)
    # first/last pair per q chunk (pairs are grouped by i, ascending j)
    first = jnp.asarray(
        [idx == 0 or pairs[idx - 1][0] != p[0] for idx, p in enumerate(pairs)]
    )
    last = jnp.asarray(
        [idx == len(pairs) - 1 or pairs[idx + 1][0] != p[0]
         for idx, p in enumerate(pairs)]
    )

    q_sc = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def body(carry, pair):
        out_buf, out_acc, m, l = carry
        i, j, is_first, is_last = pair
        qc = jax.lax.dynamic_slice_in_dim(q_sc, i * chunk_q, chunk_q, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(k, j * chunk_kv, chunk_kv, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, j * chunk_kv, chunk_kv, axis=1)
        # reset carry at the first pair of each q chunk
        m = jnp.where(is_first, jnp.full_like(m, -jnp.inf), m)
        l = jnp.where(is_first, jnp.zeros_like(l), l)
        acc = jnp.where(is_first, jnp.zeros_like(out_acc), out_acc)

        if rep > 1:
            # grouped GQA: contract against KV without materializing repeats
            qg = qc.reshape(*qc.shape[:2], kv, rep, d)
            s = jnp.einsum(
                "bqgrd,bkgd->bgrqk", qg, kc, preferred_element_type=jnp.float32
            ).reshape(qc.shape[0], h, chunk_q, chunk_kv)
        else:
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", qc, kc, preferred_element_type=jnp.float32
            )
        # intra-pair mask (diagonal chunks / window edges)
        qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
        kpos = j * chunk_kv + jnp.arange(chunk_kv)
        mask = jnp.ones((chunk_q, chunk_kv), dtype=bool)
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))          # [B, H, cq]
        # guard: fully-masked rows keep m = -inf; exp(-inf - -inf) -> use safe sub
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        if rep > 1:
            pg = p.reshape(p.shape[0], kv, rep, chunk_q, chunk_kv)
            upd = jnp.einsum(
                "bgrqk,bkgd->bgrqd", pg, vc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).reshape(p.shape[0], h, chunk_q, d)
        else:
            upd = jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
        acc = acc * alpha[..., None] + upd
        # write the finished q chunk into the output on its last pair
        safe_l = jnp.maximum(l_new, 1e-30)
        finished = (acc / safe_l[..., None]).transpose(0, 2, 1, 3)  # [B,cq,H,D]
        cur = jax.lax.dynamic_slice_in_dim(out_buf, i * chunk_q, chunk_q, 1)
        new = jnp.where(is_last, finished.astype(out_buf.dtype), cur)
        out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, new, i * chunk_q, 1)
        return (out_buf, acc, m_new, l_new), None

    carry = (
        jnp.zeros((b, sq, h, d), jnp.float32),
        jnp.zeros((b, h, chunk_q, d), jnp.float32),
        jnp.full((b, h, chunk_q), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, chunk_q), jnp.float32),
    )
    body = jax.checkpoint(body, prevent_cse=False)
    (out, _, _, _), _ = jax.lax.scan(body, carry, (qi, kj, first, last))
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,        # [B, 1, H, D]
    k_cache: jax.Array,  # [B, S, KV, D]  (includes the slot for the new token)
    v_cache: jax.Array,
    length: jax.Array,   # [] current valid length (new token already inserted)
    *,
    grouped: bool = True,
) -> jax.Array:
    """Single-token attention over a (possibly sequence-sharded) KV cache.

    Written as plain masked softmax over the cache; under pjit with the cache
    S-axis sharded on "data", GSPMD turns the max/sum reductions into the
    flash-decoding partial-softmax + combine pattern (SP for long_500k).

    grouped=True (default, §Perf iteration 1): GQA via a grouped einsum —
    q reshaped to [B, 1, KV, rep, D] contracts against the cache directly, so
    the rep× repeat of K/V is NEVER materialized. The repeat path (grouped=
    False) is kept as the measured §Perf baseline: its HLO "bytes accessed"
    carries ~8x the KV cache per layer.
    """
    b, s, kv, d = k_cache.shape
    h = q.shape[2]
    rep = h // kv
    scale = 1.0 / math.sqrt(d)
    pos = jnp.arange(s)
    valid = pos < length
    if grouped and rep > 1:
        qg = q.reshape(b, 1, kv, rep, d).astype(jnp.float32) * scale
        s_logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qg, k_cache.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [B, KV, rep, 1, S]
        s_logits = jnp.where(valid[None, None, None, None, :], s_logits,
                             -jnp.inf)
        p = jax.nn.softmax(s_logits, axis=-1)
        out = jnp.einsum(
            "bgrqk,bkgd->bqgrd", p, v_cache.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, 1, h, d).astype(q.dtype)
    kr = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    vr = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    s_logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kr.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [B, H, 1, S]
    s_logits = jnp.where(valid[None, None, None, :], s_logits, -jnp.inf)
    p = jax.nn.softmax(s_logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, vr.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


class LayerWeight(NamedTuple):
    """A reuse site's weight as the whole layer stack `[L, K, N]` with the
    scan's layer index: the site reads a tile-aligned layer's tiles from the
    stack, so the decode scan makes no per-layer copy of the weight."""

    stack: jax.Array
    layer: jax.Array


# Where each reuse site of a plain block or a pattern stack reads its weight
# in the stacked parameters (group, name); decode hands these to their sites
# as LayerWeights. Sites of other blocks (moe_shared_*, attn_global_*,
# mlp_global_*, shared_attn_*, shared_mlp_*, the RWKV mixers') get the
# weight the scan sliced.
SITE_WEIGHTS = {
    "attn_qkv": ("attn", "wqkv"),
    "attn_out": ("attn", "wo"),
    "mlp_in": ("mlp", "wi"),
    "mlp_out": ("mlp", "wo"),
    "mamba_in": ("mamba", "in_proj"),
    "mamba_out": ("mamba", "out_proj"),
}


def _maybe_reuse_matmul(name, x, w, b, reuse_ctx):
    """Route a linear site through the ReuseEngine when serving with reuse.
    A LayerWeight comes only with a site the cache holds."""
    if reuse_ctx is not None:
        engine, cache, stats = reuse_ctx
        if name in cache:
            w, layer = w if isinstance(w, LayerWeight) else (w, None)
            out, new_entry, st = engine.apply(name, x, w, b, cache[name],
                                              layer=layer)
            cache[name] = new_entry
            stats[name] = st
            return out
    out = jnp.einsum("...k,kn->...n", x, w, preferred_element_type=jnp.float32)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out.astype(x.dtype)


def attention_forward(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                   # [B, S, d]
    *,
    layer_window: int | None,       # None = full; int = sliding window
    positions: jax.Array,           # [B, S] (or [3, B, S] for mrope)
    kv_cache: dict | None = None,   # decode: {"k": [B,Sc,KV,D], "v": ...}
    kv_len: jax.Array | None = None,  # [] valid length before this token
    reuse_ctx=None,
    site_prefix: str = "attn",
) -> tuple[jax.Array, dict | None]:
    b, s, _ = x.shape
    # "attention" holds all but the q/k/v and output projections, which
    # carry their reuse site's scope in decode
    with jax.named_scope("attention"):
        h = apply_norm(p["norm"], x, cfg.norm_eps)
    qkv = _maybe_reuse_matmul(
        f"{site_prefix}_qkv", h, p["wqkv"], p.get("bqkv"), reuse_ctx
    )
    with jax.named_scope("attention"):
        q, k, v = _split_qkv(cfg, qkv)

        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)

        if cfg.rope == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        elif cfg.rope == "mrope":
            q = apply_mrope(q, positions, cfg.rope_theta, _mrope_sections(cfg))
            k = apply_mrope(k, positions, cfg.rope_theta, _mrope_sections(cfg))

        def to_cache(t):
            """Cache layout transform: duplicate KV heads to kv_heads_eff (so the
            cache head dim shards across TP) and optionally quantize to int8."""
            if cfg.kv_heads_eff != cfg.n_kv_heads:
                assert cfg.kv_heads_eff % cfg.n_kv_heads == 0
                t = jnp.repeat(t, cfg.kv_heads_eff // cfg.n_kv_heads, axis=2)
            if cfg.kv_cache_quant:
                t = jnp.clip(
                    jnp.round(t.astype(jnp.float32) / cfg.kv_quant_scale),
                    -127, 127,
                ).astype(jnp.int8)
            return t

        def from_cache(t):
            if cfg.kv_cache_quant:
                return (t.astype(jnp.float32) * cfg.kv_quant_scale).astype(x.dtype)
            return t

        new_cache = None
        if kv_cache is None:
            out = blockwise_attention(
                q, k, v,
                causal=cfg.causal,
                window=layer_window,
                chunk_q=cfg.attn_chunk_q,
                chunk_kv=cfg.attn_chunk_kv,
            )
        elif s > 1:
            # Prefill into a fresh cache: blockwise attention over the new
            # sequence, then write K/V into the cache (rolling layout for windowed
            # layers: token t lives at slot t % cache_len, matching decode).
            cache_len = kv_cache["k"].shape[1]
            out = blockwise_attention(
                q, k, v,
                causal=cfg.causal,
                window=layer_window,
                chunk_q=cfg.attn_chunk_q,
                chunk_kv=cfg.attn_chunk_kv,
            )
            kq, vq = to_cache(k), to_cache(v)
            rolling = layer_window is not None and layer_window <= cache_len
            if rolling and s >= cache_len:
                slots = jnp.arange(s - cache_len, s) % cache_len
                kc = kv_cache["k"].at[:, slots].set(kq[:, s - cache_len:])
                vc = kv_cache["v"].at[:, slots].set(vq[:, s - cache_len:])
            else:
                n = min(s, cache_len)
                kc = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], kq[:, :n], 0, 1)
                vc = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], vq[:, :n], 0, 1)
            new_cache = {"k": kc, "v": vc}
        else:
            # Decode: insert the new token. Windowed layers use a rolling cache of
            # size `window` (slot = len % cache_len); since softmax over the valid
            # set is order-independent and RoPE is applied pre-cache with absolute
            # positions, no extra window masking is needed — the cache only ever
            # holds the last `window` tokens.
            cache_len = kv_cache["k"].shape[1]
            length = kv_len
            if layer_window is not None and layer_window <= cache_len:
                slot = length % cache_len
            else:
                slot = jnp.minimum(length, cache_len - 1)
            kc = jax.lax.dynamic_update_index_in_dim(
                kv_cache["k"], to_cache(k)[:, 0], slot, 1)
            vc = jax.lax.dynamic_update_index_in_dim(
                kv_cache["v"], to_cache(v)[:, 0], slot, 1)
            new_cache = {"k": kc, "v": vc}
            out = decode_attention(q, from_cache(kc), from_cache(vc), length + 1)

        out = out.reshape(b, s, cfg.q_dim)
    out = _maybe_reuse_matmul(f"{site_prefix}_out", out, p["wo"], None, reuse_ctx)
    return out.astype(x.dtype), new_cache


def _mrope_sections(cfg: ModelConfig):
    half = cfg.head_dim // 2
    t = half - 2 * (3 * half // 8)
    return (t, 3 * half // 8, 3 * half // 8)


# ------------------------------------------------------------------------ mlp

def init_mlp(cfg: ModelConfig, key, d_ff: int | None = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {
            "wi": _dense_init(ks[0], (d, 2 * f), dtype=cfg.dtype),  # [gate | up]
            "wo": _dense_init(ks[1], (f, d), dtype=cfg.dtype),
            "norm": init_norm(d),
        }
    return {
        "wi": _dense_init(ks[0], (d, f), dtype=cfg.dtype),
        "wo": _dense_init(ks[1], (f, d), dtype=cfg.dtype),
        "norm": init_norm(d),
    }


def mlp_forward(
    p: Params, cfg: ModelConfig, x: jax.Array, *, reuse_ctx=None,
    site_prefix: str = "mlp",
) -> jax.Array:
    h = apply_norm(p["norm"], x, cfg.norm_eps)
    hi = _maybe_reuse_matmul(f"{site_prefix}_in", h, p["wi"], None, reuse_ctx)
    if cfg.mlp_kind == "swiglu":
        gate, up = jnp.split(hi, 2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.mlp_kind == "gelu":
        act = jax.nn.gelu(hi.astype(jnp.float32)).astype(x.dtype)
    elif cfg.mlp_kind == "relu2":
        r = jnp.maximum(hi.astype(jnp.float32), 0.0)
        act = (r * r).astype(x.dtype)
    else:
        raise ValueError(cfg.mlp_kind)
    out = _maybe_reuse_matmul(f"{site_prefix}_out", act, p["wo"], None, reuse_ctx)
    return out.astype(x.dtype)
