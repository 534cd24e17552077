"""Model composition: superblocks, scan-over-layers, train/prefill/decode.

Heterogeneous layer patterns are expressed as *superblocks* — the smallest
repeating group of layers — and the model scans over stacked superblocks:

  dense / moe / vlm   1 superblock = [attn, (mlp | moe)]
  gemma3 (5:1)        1 superblock = 5×[local attn, mlp] + 1×[global attn, mlp]
  rwkv6               1 superblock = [time-mix, channel-mix]
  zamba2 (hybrid)     1 superblock = 6×[mamba2] + 1×[shared attn+mlp block]
                      (shared block params live OUTSIDE the scan — weights are
                      shared across its 9 applications, per the paper)
  hubert (encoder)    1 superblock = [bidirectional attn, mlp], no decode path

A pattern stack (nemotron-h: `layer_pattern`, one letter a layer) is the
exception: its layers differ by kind and follow no period, so each kind's
parameters, decode state and reuse caches are stacked by kind, and the
layers run in pattern order, each reading its kind's stacks at its index
within the kind.

Scanning keeps the lowered HLO O(1) in depth (the dry-run compiles one
superblock body), and per-superblock state (KV caches, SSM states, reuse
caches) is sliced by the same scan.

Per-layer reuse control rides that slicing: every reuse-cache entry carries
an array-resident ctrl block (per-layer kernelMode ids, live thresholds,
budget occupancy — see repro.core.reuse_cache), so the scan that hands the
superblock body its layer's prev_q/prev_out hands it that layer's control
lane too. The layer body branches on the sliced mode id with lax.cond inside
reuse_linear — a deep stack runs mixed reuse/basic modes in ONE trace, and a
host-side per-layer mode flip between steps never retraces the scan.

The reuse sites' weights are the exception in decode through a reuse engine:
the scan leaves them whole and hands each site its stack with the scan's
layer index (a LayerWeight), so the GEMM kernel reads a tile-aligned
layer's tiles in place instead of from a per-layer copy.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LAYER_KINDS, ModelConfig
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    SITE_WEIGHTS,
    LayerWeight,
    Params,
    _dense_init,
    apply_norm,
    attention_forward,
    init_attention,
    init_mlp,
    init_norm,
    mlp_forward,
)

# --------------------------------------------------------------------- params


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Params:
    """Random parameters for `cfg`, built in ONE compiled program: each f32
    draw is fused with its bf16 cast, so peak memory stays near the bf16
    footprint instead of holding an f32 copy of every weight."""
    keys = jax.random.split(key, cfg.n_superblocks + 4)
    p: Params = {}

    if cfg.frontend == "audio":
        # stub frontend: precomputed frame embeddings arrive at d_model width
        p["embed_proj"] = _dense_init(
            keys[-1], (cfg.d_model, cfg.d_model), dtype=cfg.dtype
        )
    else:
        p["embed"] = (
            jax.random.normal(keys[-1], (cfg.vocab, cfg.d_model), jnp.float32)
            * 0.01
        ).astype(cfg.dtype)

    def init_superblock(k):
        return _init_superblock(cfg, k)

    if cfg.layer_pattern:
        p["blocks"] = _init_pattern(cfg, keys[: cfg.n_layers])
    elif cfg.scan_layers:
        p["blocks"] = jax.vmap(init_superblock)(keys[: cfg.n_superblocks])
    else:
        blocks = [init_superblock(k) for k in keys[: cfg.n_superblocks]]
        p["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    if cfg.hybrid_attn_every:
        p["shared_block"] = {
            "attn": init_attention(cfg, keys[-2]),
            "mlp": init_mlp(cfg, keys[-3]),
        }

    p["final_norm"] = init_norm(cfg.d_model)
    if not cfg.tie_embeddings or cfg.frontend == "audio":
        p["lm_head"] = _dense_init(keys[-4], (cfg.d_model, cfg.vocab), dtype=cfg.dtype)
    return p


_KIND_INIT = {"M": ssm_mod.init_mamba2, "-": init_mlp, "*": init_attention}


def _init_pattern(cfg: ModelConfig, keys) -> Params:
    """{group: the kind's layers stacked} of a pattern stack, layer l drawn
    from keys[l]."""
    out = {}
    for letter, group in LAYER_KINDS.items():
        idx = [i for i, c in enumerate(cfg.layer_pattern) if c == letter]
        if idx:
            out[group] = jax.vmap(
                functools.partial(_KIND_INIT[letter], cfg))(keys[np.array(idx)])
    return out


def _init_superblock(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, max(cfg.superblock_layers * 2, 4))
    if cfg.ssm_kind == "rwkv6":
        return {"rwkv": ssm_mod.init_rwkv6(cfg, ks[0])}
    if cfg.ssm_kind == "mamba2":
        inner = [ssm_mod.init_mamba2(cfg, k) for k in ks[: cfg.hybrid_attn_every]]
        return {"mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *inner)}
    if cfg.attn_kind == "local_global":
        local = [
            {"attn": init_attention(cfg, ks[2 * i]), "mlp": init_mlp(cfg, ks[2 * i + 1])}
            for i in range(cfg.local_ratio)
        ]
        return {
            "local": jax.tree.map(lambda *xs: jnp.stack(xs), *local),
            "global": {
                "attn": init_attention(cfg, ks[-2]),
                "mlp": init_mlp(cfg, ks[-1]),
            },
        }
    block: Params = {"attn": init_attention(cfg, ks[0])}
    if cfg.n_experts:
        block["moe"] = moe_mod.init_moe(cfg, ks[1])
    else:
        block["mlp"] = init_mlp(cfg, ks[1])
    return block


# --------------------------------------------------------------- decode state


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    """Per-arch serving state: KV caches (full or rolling), SSM states, pos."""
    nsb = cfg.n_superblocks
    kvd = (cfg.kv_heads_eff, cfg.head_dim)
    kv_dtype = jnp.int8 if cfg.kv_cache_quant else cfg.dtype

    def kv(seq):
        return {
            "k": jnp.zeros((batch, seq, *kvd), kv_dtype),
            "v": jnp.zeros((batch, seq, *kvd), kv_dtype),
        }

    def stack(n, tree):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n, *x.shape)).copy(), tree)

    state: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.layer_pattern:
        state["blocks"] = {}
        if cfg.kind_layers("M"):
            state["blocks"]["mamba"] = stack(
                cfg.kind_layers("M"), ssm_mod.init_mamba2_state(cfg, batch))
        if cfg.kind_layers("*"):
            state["blocks"]["attn"] = stack(cfg.kind_layers("*"),
                                            kv(cache_len))
    elif cfg.ssm_kind == "rwkv6":
        state["blocks"] = stack(nsb, ssm_mod.init_rwkv6_state(cfg, batch))
    elif cfg.ssm_kind == "mamba2":
        blocks = stack(
            nsb, stack(cfg.hybrid_attn_every, ssm_mod.init_mamba2_state(cfg, batch))
        )
        state["blocks"] = {"mamba": blocks}
        if cfg.hybrid_attn_every:
            state["blocks"]["shared_kv"] = stack(nsb, kv(cache_len))
    elif cfg.attn_kind == "local_global":
        w = min(cfg.window, cache_len)
        state["blocks"] = {
            "local": stack(nsb, stack(cfg.local_ratio, kv(w))),
            "global": stack(nsb, kv(cache_len)),
        }
    elif cfg.attn_kind == "swa":
        state["blocks"] = stack(nsb, kv(min(cfg.window, cache_len)))
    else:
        state["blocks"] = stack(nsb, kv(cache_len))
    return state


# ------------------------------------------------------------------- forward


def _layer_window(cfg: ModelConfig, kind: str) -> int | None:
    if kind == "local":
        return cfg.window
    if kind == "swa":
        return cfg.window
    return None


def _block_forward(
    cfg: ModelConfig,
    bp: Params,
    x: jax.Array,
    bstate: dict | None,
    *,
    positions,
    shared_block: Params | None,
    kv_len=None,
    reuse_ctx=None,
    decode: bool,
):
    """One superblock. Returns (x, new_bstate)."""
    new_state: dict[str, Any] = {}

    if cfg.ssm_kind == "rwkv6":
        st = bstate if bstate is not None else ssm_mod.init_rwkv6_state(
            cfg, x.shape[0]
        )
        h, tstate = ssm_mod.rwkv6_time_mix(
            bp["rwkv"], cfg, apply_norm(bp["rwkv"]["norm1"], x, cfg.norm_eps),
            st["tmix"], reuse_ctx=reuse_ctx,
        )
        x = x + h
        h, cstate = ssm_mod.rwkv6_channel_mix(
            bp["rwkv"], cfg, apply_norm(bp["rwkv"]["norm2"], x, cfg.norm_eps),
            st["cmix"], reuse_ctx=reuse_ctx,
        )
        x = x + h
        return x, {"tmix": tstate, "cmix": cstate}

    if cfg.ssm_kind == "mamba2":
        st = bstate["mamba"] if bstate is not None else None

        def mamba_body(carry, xs):
            xx = carry
            mp, ms = xs
            with jax.named_scope("layer"):
                h, new_ms = ssm_mod.mamba2_forward(mp, cfg, xx, ms,
                                                   reuse_ctx=None)
                return xx + h, new_ms

        if st is None:
            st = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.hybrid_attn_every, *a.shape)
                ).copy(),
                ssm_mod.init_mamba2_state(cfg, x.shape[0]),
            )
        with jax.named_scope("layer_scan"):
            x, new_ms = jax.lax.scan(mamba_body, x, (bp["mamba"], st))
        new_state["mamba"] = new_ms
        if shared_block is not None:
            kv = bstate.get("shared_kv") if (bstate and decode) else None
            h, new_kv = attention_forward(
                shared_block["attn"], cfg, x,
                layer_window=None, positions=positions,
                kv_cache=kv, kv_len=kv_len, reuse_ctx=reuse_ctx,
                site_prefix="shared_attn",
            )
            x = x + h
            x = x + mlp_forward(
                shared_block["mlp"], cfg, x, reuse_ctx=reuse_ctx,
                site_prefix="shared_mlp",
            )
            if decode:
                new_state["shared_kv"] = new_kv
        return x, new_state

    if cfg.attn_kind == "local_global":
        # Inner local layers run without reuse_ctx: their caches would need a
        # second stacking level; reuse rides on the outer (global) sites.
        def local_body(carry, xs):
            xx = carry
            lp, lkv = xs
            with jax.named_scope("layer"):
                h, new_kv = attention_forward(
                    lp["attn"], cfg, xx, layer_window=cfg.window,
                    positions=positions, kv_cache=lkv, kv_len=kv_len,
                    reuse_ctx=None, site_prefix="attn_local",
                )
                xx = xx + h
                xx = xx + mlp_forward(lp["mlp"], cfg, xx, reuse_ctx=None)
                return xx, new_kv

        if decode:
            with jax.named_scope("layer_scan"):
                x, new_lkv = jax.lax.scan(
                    local_body, x, (bp["local"], bstate["local"]))
            new_state["local"] = new_lkv
        else:
            x, _ = _unstacked_local(cfg, bp, x, positions, reuse_ctx)
        gkv = bstate["global"] if (bstate is not None and decode) else None
        h, new_gkv = attention_forward(
            bp["global"]["attn"], cfg, x, layer_window=None,
            positions=positions, kv_cache=gkv, kv_len=kv_len,
            reuse_ctx=reuse_ctx, site_prefix="attn_global",
        )
        x = x + h
        x = x + mlp_forward(
            bp["global"]["mlp"], cfg, x, reuse_ctx=reuse_ctx,
            site_prefix="mlp_global",
        )
        if decode:
            new_state["global"] = new_gkv
        return x, new_state

    # plain dense / moe / swa / encoder block
    window = cfg.window if cfg.attn_kind == "swa" else None
    kv = bstate if (bstate is not None and decode) else None
    h, new_kv = attention_forward(
        bp["attn"], cfg, x, layer_window=window, positions=positions,
        kv_cache=kv, kv_len=kv_len, reuse_ctx=reuse_ctx,
    )
    x = x + h
    if cfg.n_experts:
        x = x + moe_mod.moe_forward(bp["moe"], cfg, x, reuse_ctx=reuse_ctx)
    else:
        x = x + mlp_forward(bp["mlp"], cfg, x, reuse_ctx=reuse_ctx)
    return x, (new_kv if decode else {})


def _pattern_layer(cfg: ModelConfig, letter: str, lp: Params, x, st, *,
                   positions, kv_len, reuse_ctx, state_layer):
    """One layer of a pattern stack: its mixer, with its own pre-norm, and
    the residual. Returns (x, the layer's new state or None). A Mamba2
    mixer given `state_layer` takes its kind's state stacks whole and
    updates its layer's state in place."""
    if letter == "M":
        if st is None:
            st = ssm_mod.init_mamba2_state(cfg, x.shape[0])
        # one chunk for a serving prompt of up to 512 tokens: more chunks
        # only save memory under differentiation, and in a prefill their
        # boundary copies double the temporaries
        h, st = ssm_mod.mamba2_forward(lp, cfg, x, st, reuse_ctx=reuse_ctx,
                                       layer=state_layer, chunk=512)
    elif letter == "-":
        h = mlp_forward(lp, cfg, x, reuse_ctx=reuse_ctx)
    else:
        h, st = attention_forward(
            lp, cfg, x, layer_window=None, positions=positions, kv_cache=st,
            kv_len=kv_len, reuse_ctx=reuse_ctx)
    return x + h, st


def _put(stack, v, *, i: int):
    """`stack` with `v` written in place at index i of its leading axis."""
    return jax.lax.dynamic_update_index_in_dim(stack, v, i, 0)


def _pattern_forward(cfg: ModelConfig, blocks: Params, states, x, *,
                     positions, kv_len, stacks, reuse_engine, reuse_cache,
                     train: bool):
    """The layers of a pattern stack in order. Layer l of kind k is the
    i-th of its kind: it reads its parameters, state and reuse-cache entries
    at index i of k's stacks, and writes its state and entries back there.
    That slicing and write-back lies under `layer_scan` outside `layer`, as
    a scan's does, but for two operands read in place: each reuse site's
    weight stays whole in `stacks` and reaches the site as a LayerWeight at
    i, and a Mamba2 mixer updates its layer's SSM and conv state in the
    stack itself. Both ways compile to the same instructions with no copy of
    the state stack; but XLA names the fused recurrence after its root, the
    state's write-back, so writing it inside the mixer keeps the recurrence
    under `ssm_update`. Returns (x, states, rcache)."""
    states = dict(states) if states is not None else None
    rcache = dict(reuse_cache) if reuse_cache is not None else None
    seen = dict.fromkeys(LAYER_KINDS, 0)
    for letter in cfg.layer_pattern:
        group, i = LAYER_KINDS[letter], seen[letter]
        seen[letter] += 1
        lp = _with_layer_weights(
            {group: jax.tree.map(lambda a: a[i], blocks[group])}, stacks,
            jnp.int32(i))[group]
        held = states is not None and group in states
        in_place = held and letter == "M"
        st = None
        if held:
            st = states[group] if in_place else jax.tree.map(
                lambda a: a[i], states[group])
        sites = [n for n in rcache or ()
                 if SITE_WEIGHTS.get(n, ("",))[0] == group]
        rctx = None
        if reuse_engine is not None and rcache is not None:
            rctx = (reuse_engine,
                    {n: jax.tree.map(lambda a: a[i], rcache[n])
                     for n in sites}, {})
        layer = functools.partial(_pattern_layer, cfg, letter,
                                  positions=positions, kv_len=kv_len,
                                  reuse_ctx=rctx,
                                  state_layer=i if in_place else None)
        if train and cfg.remat:
            layer = jax.checkpoint(layer, prevent_cse=False)
        with jax.named_scope("layer"):
            x, st = layer(lp, x, st)
        if held:
            states[group] = st if in_place else jax.tree.map(
                functools.partial(_put, i=i), states[group], st)
        if rctx is not None:
            for n in sites:
                rcache[n] = jax.tree.map(functools.partial(_put, i=i),
                                         rcache[n], rctx[1][n])
    return x, states, rcache


def _unstacked_local(cfg, bp, x, positions, reuse_ctx):
    """Training/prefill path for local layers (no KV state): scan over the
    stacked local blocks with no per-layer state."""

    def body(carry, lp):
        xx = carry
        with jax.named_scope("layer"):
            h, _ = attention_forward(
                lp["attn"], cfg, xx, layer_window=cfg.window,
                positions=positions, kv_cache=None, reuse_ctx=reuse_ctx,
                site_prefix="attn_local",
            )
            xx = xx + h
            xx = xx + mlp_forward(lp["mlp"], cfg, xx, reuse_ctx=reuse_ctx)
            return xx, None

    with jax.named_scope("layer_scan"):
        x, _ = jax.lax.scan(body, x, bp["local"])
    return x, None


# --------------------------------------------------- in-place site weights


def _lift_site_weights(blocks: Params, reuse_cache: dict):
    """Split the weight stacks of the reuse sites in `reuse_cache` off the
    scanned block parameters: (the rest, {param path: [L, K, N] stack})."""
    paths = [p for site, p in SITE_WEIGHTS.items()
             if site in reuse_cache and p[1] in blocks.get(p[0], {})]
    rest = dict(blocks)
    for group, _ in paths:
        rest[group] = dict(blocks[group])
    return rest, {(g, n): rest[g].pop(n) for g, n in paths}


def _with_layer_weights(bp: Params, stacks: dict, layer) -> Params:
    """A layer's block parameters with each lifted stack of a group they
    hold put back as a LayerWeight at its path."""
    bp = dict(bp)
    for (group, name), stack in stacks.items():
        if group in bp:
            bp[group] = {**bp[group], name: LayerWeight(stack, layer)}
    return bp


# ------------------------------------------------------------------ embedding


def embed_inputs(params: Params, cfg: ModelConfig, inputs: dict) -> jax.Array:
    if cfg.frontend == "audio":
        x = inputs["embeds"].astype(cfg.dtype)
        return jnp.einsum("bsd,de->bse", x, params["embed_proj"],
                          preferred_element_type=jnp.float32).astype(cfg.dtype)
    x = params["embed"][inputs["tokens"]]
    if "vision_embeds" in inputs and inputs["vision_embeds"] is not None:
        # VLM stub: precomputed patch embeddings overwrite their token slots
        ve = inputs["vision_embeds"].astype(x.dtype)
        vp = inputs["vision_positions"]  # [B, P] int32 positions
        x = jax.vmap(lambda xb, vb, pb: xb.at[pb].set(vb))(x, ve, vp)
    return x


def output_logits(params: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    with jax.named_scope("head"):
        h = apply_norm(params["final_norm"], h, cfg.norm_eps)
        if "lm_head" in params:
            return jnp.einsum("bsd,dv->bsv", h, params["lm_head"],
                              preferred_element_type=jnp.float32)
        return jnp.einsum("bsd,vd->bsv", h, params["embed"],
                          preferred_element_type=jnp.float32)


# -------------------------------------------------------------------- forward


def forward(
    params: Params,
    cfg: ModelConfig,
    inputs: dict,
    *,
    decode_state: dict | None = None,
    reuse_engine=None,
    reuse_cache: dict | None = None,
):
    """Returns (hidden [B,S,d], new_decode_state, new_reuse_cache, stats)."""
    decode = decode_state is not None
    with jax.named_scope("embed"):
        x = embed_inputs(params, cfg, inputs)
        b, s, _ = x.shape
        if decode:
            pos0 = decode_state["len"]
            positions = (pos0 + jnp.arange(s))[None, :].astype(jnp.int32)
            positions = jnp.broadcast_to(positions, (b, s))
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, b, s))

    shared_block = params.get("shared_block")
    bstates = decode_state["blocks"] if decode else None
    blocks, stacks = params["blocks"], {}
    if decode and reuse_engine is not None and reuse_cache is not None:
        blocks, stacks = _lift_site_weights(blocks, reuse_cache)
    layers = np.arange(cfg.n_superblocks, dtype=np.int32) if stacks else None

    stats: dict[str, Any] = {}
    if cfg.layer_pattern:
        with jax.named_scope("layer_scan"):
            x, new_bstates, new_rcache = _pattern_forward(
                cfg, blocks, bstates, x, positions=positions,
                kv_len=decode_state["len"] if decode else None,
                stacks=stacks, reuse_engine=reuse_engine,
                reuse_cache=reuse_cache, train=not decode)
            new_state = None
            if decode:
                new_state = {"len": decode_state["len"] + s,
                             "blocks": new_bstates}
        return x, new_state, new_rcache, stats

    def body(carry, xs):
        xx = carry
        # rcache is THIS superblock's slice of every reuse site's cache —
        # including the ctrl lane whose mode id the reuse dispatch branches
        # on, so kernelMode is per-layer inside the scan
        bp, bst, rcache, layer = xs
        if stacks:
            bp = _with_layer_weights(bp, stacks, layer)
        rctx = None
        if reuse_engine is not None and rcache is not None:
            rctx = (reuse_engine, rcache, {})
        with jax.named_scope("layer"):
            xx, new_bst = _block_forward(
                cfg, bp, xx, bst,
                positions=positions, shared_block=shared_block,
                kv_len=decode_state["len"] if decode else None,
                reuse_ctx=rctx, decode=decode,
            )
        new_rcache = rctx[1] if rctx is not None else rcache
        return xx, (new_bst, new_rcache)

    if cfg.remat and not decode:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)

    xs = (blocks, bstates, reuse_cache, layers)
    # Ops under "layer_scan" but outside "layer" are the scan's own per-layer
    # slicing and write-back of its operands (KV state, reuse cache, and the
    # weights that no reuse site reads in place).
    with jax.named_scope("layer_scan"):
        x, (new_bstates, new_rcache) = jax.lax.scan(body, x, xs)
        new_state = None
        if decode:
            new_state = {"len": decode_state["len"] + s, "blocks": new_bstates}
    return x, new_state, new_rcache, stats
