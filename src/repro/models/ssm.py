"""Attention-free sequence mixers: RWKV6 (Finch) and Mamba2 (SSD).

Both recurrences are evaluated with a two-level chunked scan: an outer
`lax.scan` over time chunks (rematerialized, so only chunk-boundary states are
saved for backward) and an inner `lax.scan` over steps. This bounds training
memory at seq 4k and keeps the lowered HLO small (the dry-run compiles the
body once per level). Decode is the single-step recurrence with the state
carried in the decode-state pytree — O(1) in context length, which is what
makes the long_500k cell runnable for these families.

RWKV6 (arXiv:2404.05892): token-shift with data-dependent (LoRA) mixing,
data-dependent per-channel decay w_t, bonus u, per-head state S ∈ R^{dk×dv}:

    out_t = r_t · (diag(u)·k_tᵀ v_t + S_t);   S_{t+1} = diag(w_t)·S_t + k_tᵀ v_t

Mamba2 (arXiv:2405.21060): scalar-per-head decay a_t = exp(dt_t·A), state
h ∈ R^{heads×headdim×state}, B and C shared by the heads of a group:

    h_t = a_t·h_{t-1} + dt_t · x_t ⊗ B_t;     y_t = h_t · C_t + D·x_t
    out = W_out · groupnorm(y ⊙ silu(z))      (gate first, then the norm)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, _dense_init, apply_norm, init_norm, rms_norm


def _chunked_scan(step_fn, state, xs, chunk: int):
    """Outer-remat / inner-step scan over the time axis of every leaf in xs."""
    length = jax.tree.leaves(xs)[0].shape[0]
    while length % chunk:
        chunk -= 1  # largest divisor <= requested (handles odd smoke shapes)
    n_chunks = length // chunk
    xs_c = jax.tree.map(
        lambda a: a.reshape(n_chunks, chunk, *a.shape[1:]), xs
    )

    def inner(state, xs_chunk):
        return jax.lax.scan(step_fn, state, xs_chunk)

    inner = jax.checkpoint(inner, prevent_cse=False)
    state, ys = jax.lax.scan(inner, state, xs_c)
    ys = jax.tree.map(lambda a: a.reshape(length, *a.shape[2:]), ys)
    return state, ys


# ------------------------------------------------------------------- RWKV6

RWKV_LORA = 32
RWKV_DECAY_LORA = 64


def init_rwkv6(cfg: ModelConfig, key) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.ssm_head_dim
    n_h = d // hd
    ks = jax.random.split(key, 12)
    s = 1.0 / math.sqrt(d)
    return {
        "norm1": init_norm(d),
        "norm2": init_norm(d),
        "tmix": {
            "maa_x": jnp.zeros((d,), jnp.float32),
            "maa_wkvrg": jnp.zeros((5, d), jnp.float32),
            "tm_w1": _dense_init(ks[0], (d, 5 * RWKV_LORA), dtype=cfg.dtype),
            "tm_w2": (jax.random.normal(ks[1], (5, RWKV_LORA, d), jnp.float32)
                      * 0.01).astype(cfg.dtype),
            "td_w1": _dense_init(ks[2], (d, RWKV_DECAY_LORA), dtype=cfg.dtype),
            "td_w2": (jax.random.normal(ks[3], (RWKV_DECAY_LORA, d), jnp.float32)
                      * 0.01).astype(cfg.dtype),
            "decay_base": jnp.full((d,), -6.0, jnp.float32),
            "wr": _dense_init(ks[4], (d, d), dtype=cfg.dtype),
            "wk": _dense_init(ks[5], (d, d), dtype=cfg.dtype),
            "wv": _dense_init(ks[6], (d, d), dtype=cfg.dtype),
            "wg": _dense_init(ks[7], (d, d), dtype=cfg.dtype),
            "wo": _dense_init(ks[8], (d, d), dtype=cfg.dtype),
            "bonus": jnp.zeros((n_h, hd), jnp.float32),
            "ln_x": init_norm(d),
        },
        "cmix": {
            "maa_k": jnp.zeros((d,), jnp.float32),
            "maa_r": jnp.zeros((d,), jnp.float32),
            "wk": _dense_init(ks[9], (d, f), dtype=cfg.dtype),
            "wv": _dense_init(ks[10], (f, d), dtype=cfg.dtype),
            "wr": _dense_init(ks[11], (d, d), dtype=cfg.dtype),
        },
    }


def _rwkv_projections(p: Params, cfg: ModelConfig, x, x_shift, reuse_ctx, prefix):
    """Token-shift mixing + r/k/v/g/decay projections. x: [B, S, d]."""
    from repro.models.layers import _maybe_reuse_matmul

    tm = p["tmix"]
    sx = x_shift - x
    xxx = x + sx * tm["maa_x"].astype(x.dtype)
    router = jnp.tanh(
        jnp.einsum("bsd,dl->bsl", xxx, tm["tm_w1"],
                   preferred_element_type=jnp.float32)
    ).reshape(*x.shape[:2], 5, RWKV_LORA)
    mix = jnp.einsum("bsfl,fld->bsfd", router.astype(x.dtype), tm["tm_w2"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    maa = tm["maa_wkvrg"].astype(x.dtype)
    xw, xk, xv, xr, xg = [
        x + sx * (maa[i] + mix[:, :, i]) for i in range(5)
    ]
    r = _maybe_reuse_matmul(f"{prefix}_wr", xr, tm["wr"], None, reuse_ctx)
    k = _maybe_reuse_matmul(f"{prefix}_wk", xk, tm["wk"], None, reuse_ctx)
    v = _maybe_reuse_matmul(f"{prefix}_wv", xv, tm["wv"], None, reuse_ctx)
    g = jax.nn.silu(
        _maybe_reuse_matmul(f"{prefix}_wg", xg, tm["wg"], None, reuse_ctx)
        .astype(jnp.float32)
    ).astype(x.dtype)
    decay_in = jnp.tanh(
        jnp.einsum("bsd,dl->bsl", xw, tm["td_w1"],
                   preferred_element_type=jnp.float32)
    )
    decay = tm["decay_base"] + jnp.einsum(
        "bsl,ld->bsd", decay_in.astype(x.dtype), tm["td_w2"],
        preferred_element_type=jnp.float32,
    )
    w = jnp.exp(-jnp.exp(decay.astype(jnp.float32)))  # [B, S, d] in (0, 1)
    return r, k, v, g, w


def rwkv6_time_mix(
    p: Params, cfg: ModelConfig, x: jax.Array, state: dict, *,
    reuse_ctx=None, prefix: str = "rwkv",
) -> tuple[jax.Array, dict]:
    """x: [B, S, d]; state: {"shift": [B, d], "wkv": [B, H, dk, dv]}."""
    b, s, d = x.shape
    hd = cfg.ssm_head_dim
    n_h = d // hd
    tm = p["tmix"]

    x_shift = jnp.concatenate([state["shift"][:, None], x[:, :-1]], axis=1)
    r, k, v, g, w = _rwkv_projections(p, cfg, x, x_shift, reuse_ctx, prefix)

    rh = r.reshape(b, s, n_h, hd).astype(jnp.float32)
    kh = k.reshape(b, s, n_h, hd).astype(jnp.float32)
    vh = v.reshape(b, s, n_h, hd).astype(jnp.float32)
    wh = w.reshape(b, s, n_h, hd)
    u = tm["bonus"].astype(jnp.float32)

    def step(wkv, ins):
        r_t, k_t, v_t, w_t = ins          # [B, H, hd]
        kv = k_t[..., :, None] * v_t[..., None, :]     # [B, H, dk, dv]
        out = jnp.einsum("bhk,bhkv->bhv", r_t, u[None, :, :, None] * kv + wkv)
        wkv = w_t[..., :, None] * wkv + kv
        return wkv, out

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rh, kh, vh, wh))  # [S, B, H, hd]
    wkv, outs = _chunked_scan(step, state["wkv"], xs, chunk=min(s, 256))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, d)              # [B, S, d]

    out = rms_norm(out.astype(x.dtype), tm["ln_x"]["scale"], cfg.norm_eps) * g
    from repro.models.layers import _maybe_reuse_matmul

    out = _maybe_reuse_matmul(f"{prefix}_wo", out, tm["wo"], None, reuse_ctx)
    new_state = {"shift": x[:, -1], "wkv": wkv}
    return out.astype(x.dtype), new_state


def rwkv6_channel_mix(
    p: Params, cfg: ModelConfig, x: jax.Array, state: dict, *,
    reuse_ctx=None, prefix: str = "rwkv_cmix",
) -> tuple[jax.Array, dict]:
    from repro.models.layers import _maybe_reuse_matmul

    cm = p["cmix"]
    x_shift = jnp.concatenate([state["shift"][:, None], x[:, :-1]], axis=1)
    sx = x_shift - x
    xk = x + sx * cm["maa_k"].astype(x.dtype)
    xr = x + sx * cm["maa_r"].astype(x.dtype)
    k = _maybe_reuse_matmul(f"{prefix}_wk", xk, cm["wk"], None, reuse_ctx)
    k = jnp.square(jnp.maximum(k.astype(jnp.float32), 0.0)).astype(x.dtype)
    kv = _maybe_reuse_matmul(f"{prefix}_wv", k, cm["wv"], None, reuse_ctx)
    r = _maybe_reuse_matmul(f"{prefix}_wr", xr, cm["wr"], None, reuse_ctx)
    out = jax.nn.sigmoid(r.astype(jnp.float32)).astype(x.dtype) * kv
    return out, {"shift": x[:, -1]}


def init_rwkv6_state(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    hd = cfg.ssm_head_dim
    n_h = d // hd
    return {
        "tmix": {
            "shift": jnp.zeros((batch, d), cfg.dtype),
            "wkv": jnp.zeros((batch, n_h, hd, hd), jnp.float32),
        },
        "cmix": {"shift": jnp.zeros((batch, d), cfg.dtype)},
    }


# ------------------------------------------------------------------- Mamba2

MAMBA_CONV_K = 4


def init_mamba2(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    di = cfg.d_inner
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    nh = cfg.n_ssm_heads
    ks = jax.random.split(key, 4)
    conv_ch = di + bc
    return {
        "norm": init_norm(d),
        "in_proj": _dense_init(ks[0], (d, 2 * di + bc + nh), dtype=cfg.dtype),
        "conv_w": (jax.random.normal(ks[1], (MAMBA_CONV_K, conv_ch), jnp.float32)
                   * 0.1).astype(cfg.dtype),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_norm": init_norm(di),
        "out_proj": _dense_init(ks[2], (di, d), dtype=cfg.dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array, conv_state: jax.Array):
    """Depthwise causal conv, kernel K, in float32. x: [B, S, C];
    conv_state: [B, K-1, C], the last K-1 inputs, kept in x's dtype."""
    k = w.shape[0]
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    xf = xp.astype(jnp.float32)
    out = sum(
        xf[:, i : i + x.shape[1]] * w[i].astype(jnp.float32) for i in range(k)
    )
    out = out + b.astype(jnp.float32)
    new_state = xp[:, -(k - 1):] if k > 1 else conv_state
    return out, new_state


def _gated_group_norm(y, z, scale, groups: int, eps: float):
    """Mamba2's gated RMSNorm as published (`norm_before_gate=False`): gate
    y by silu(z) first, then normalise each of `groups` equal channel groups
    on its own. Float32 in and out."""
    g = y * jax.nn.silu(z)
    gg = g.reshape(*g.shape[:-1], groups, -1)
    gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + eps)
    return gg.reshape(g.shape) * (1.0 + scale.astype(jnp.float32))


def mamba2_forward(
    p: Params, cfg: ModelConfig, x: jax.Array, state: dict, *,
    reuse_ctx=None, prefix: str = "mamba", layer: int | None = None,
    chunk: int = 256,
) -> tuple[jax.Array, dict]:
    """x: [B, S, d]; state: {"conv": [B, K-1, C], "h": [B, nh, hd, state]}.
    With `layer`, each state leaf is a stack of layers [L, ...]: the mixer
    reads layer `layer` there and writes its new state back in place.
    `chunk`: the time steps of one rematerialised chunk of a prompt's scan,
    which bounds what a differentiated call keeps.

    Head h reads group h // (nh / G) of B and C. Everything but the in and
    out projections (the reuse sites) lies under the scope `ssm_update`,
    the state's read and in-place write included."""
    from repro.models.layers import _maybe_reuse_matmul

    b, s, d = x.shape
    di, st, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    g, hd = cfg.ssm_groups, cfg.ssm_head_dim
    rep, gs = nh // g, g * st

    def step(h, ins):
        # [B,nh,hd], [B,G,st], [B,G,st], [B,nh], [B,nh]
        x_t, b_t, c_t, dt_t, dec_t = ins
        hg = h.reshape(b, g, rep, hd, st)
        dx = (dt_t[..., None] * x_t).reshape(b, g, rep, hd)
        hg = (dec_t.reshape(b, g, rep)[..., None, None] * hg
              + dx[..., None] * b_t[:, :, None, None, :])
        y = jnp.einsum("bgrps,bgs->bgrp", hg, c_t)
        return hg.reshape(b, nh, hd, st), y.reshape(b, nh, hd)

    with jax.named_scope("mamba"):
        with jax.named_scope("ssm_update"):
            hin = apply_norm(p["norm"], x, cfg.norm_eps)
        zxbcdt = _maybe_reuse_matmul(
            f"{prefix}_in", hin, p["in_proj"], None, reuse_ctx
        )
        with jax.named_scope("ssm_update"):
            stacks = state
            if layer is not None:
                state = jax.tree.map(lambda a: jax.lax.index_in_dim(
                    a, layer, keepdims=False), stacks)
            z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * gs], -1)
            conv_out, conv_state = _causal_conv(
                xbc, p["conv_w"], p["conv_b"], state["conv"])
            conv_out = jax.nn.silu(conv_out)
            xc, bc, cc = jnp.split(conv_out, [di, di + gs], axis=-1)

            dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,S,nh]
            a = -jnp.exp(p["A_log"])                                     # [nh]
            decay = jnp.exp(dt * a)                                      # [B,S,nh]

            xh = xc.reshape(b, s, nh, hd)
            xs = (xh, bc.reshape(b, s, g, st), cc.reshape(b, s, g, st), dt,
                  decay)
            if s == 1:
                h, y = step(state["h"], tuple(v[:, 0] for v in xs))
                y = y[:, None]
            else:
                h, ys = _chunked_scan(
                    step, state["h"], tuple(jnp.moveaxis(v, 1, 0) for v in xs),
                    chunk=min(s, chunk))
                y = jnp.moveaxis(ys, 0, 1)                           # [B,S,nh,hd]
            y = y + p["D"][:, None] * xh
            y = _gated_group_norm(y.reshape(b, s, di), z.astype(jnp.float32),
                                 p["out_norm"]["scale"], g, cfg.norm_eps)
            y = y.astype(x.dtype)
            new = {"conv": conv_state, "h": h}
            if layer is not None:
                new = jax.tree.map(
                    lambda a, v: jax.lax.dynamic_update_index_in_dim(
                        a, v, layer, 0), stacks, new)
        out = _maybe_reuse_matmul(f"{prefix}_out", y, p["out_proj"], None,
                                  reuse_ctx)
    return out.astype(x.dtype), new


def init_mamba2_state(cfg: ModelConfig, batch: int) -> dict:
    di, st, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    return {
        "conv": jnp.zeros((batch, MAMBA_CONV_K - 1,
                           di + 2 * cfg.ssm_groups * st), cfg.dtype),
        "h": jnp.zeros((batch, nh, cfg.ssm_head_dim, st), jnp.float32),
    }
