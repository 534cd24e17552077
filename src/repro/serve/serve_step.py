"""Serving steps: prefill (S > 1 into fresh caches) and decode (S = 1).

`decode_step` is where ReuseSense lives (the paper's setting: repeated
evaluations of the same layer on consecutive inputs). The reuse cache pytree
threads through the step beside the KV cache; the engine's per-site kernelMode
has already been decided host-side (policy), so the step stays branch-free.

These are the functions the dry-run lowers for prefill_32k / decode_32k /
long_500k cells.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LAYER_KINDS, ModelConfig
from repro.core.engine import ReuseEngine
from repro.core.policy import ReusePolicy
from repro.models import forward, init_decode_state, output_logits
from repro.models.layers import SITE_WEIGHTS


def build_reuse_engine(
    cfg: ModelConfig,
    *,
    impl: str = "jnp",
    block_m: int = 8,
    block_k: int = 256,
    policy: ReusePolicy | None = None,
) -> ReuseEngine:
    """Register the decode-time reuse sites for an architecture.

    Site inventory mirrors DESIGN.md §4: attention projections + dense MLP +
    shared-expert everywhere they exist; routed experts and nested-inner sites
    are excluded (documented arch-applicability scoping).

    `policy` carries per-site tunables (see repro.tune): registration resolves
    each site's block_k, exec_path and max_active_k through it, so a tuned
    table changes both the tile granularity AND the execution substrate
    (masked kernel vs ragged compacted grid vs gathered compact GEMM) the
    site is dispatched on — and the host-side `refresh_modes` pass keeps
    promoting sites onto the compacted tier as their measured skip rate
    develops.
    """
    eng = ReuseEngine(impl=impl, policy=policy or ReusePolicy())
    nsb = cfg.n_superblocks
    d = cfg.d_model

    def reg(name, fi, fo, mode="auto"):
        eng.register(
            name, fi, fo, n_layers=nsb, block_m=block_m, block_k=block_k,
            mode=mode,
        )

    shapes = _site_shapes(cfg)
    if cfg.layer_pattern:
        # a pattern stack: each in-place site of a kind the pattern holds,
        # registered with that kind's layer count
        letter = {group: k for k, group in LAYER_KINDS.items()}
        for name, (group, _) in SITE_WEIGHTS.items():
            n = cfg.kind_layers(letter[group])
            if n:
                eng.register(name, *shapes[name], n_layers=n,
                             block_m=block_m, block_k=block_k)
        return eng
    if cfg.ssm_kind == "rwkv6":
        for nm in ("wr", "wk", "wv", "wg"):
            reg(f"rwkv_{nm}", d, d)
        reg("rwkv_wo", d, d)
        reg("rwkv_cmix_wk", d, cfg.d_ff)
        reg("rwkv_cmix_wv", cfg.d_ff, d)
        reg("rwkv_cmix_wr", d, d)
        return eng
    if cfg.ssm_kind == "mamba2":
        # zamba2's inner mamba sites are nested (excluded); the shared block
        # carries reuse
        if cfg.hybrid_attn_every:
            reg("shared_attn_qkv", d, cfg.q_dim + 2 * cfg.kv_dim)
            reg("shared_attn_out", cfg.q_dim, d)
            fi = 2 * cfg.d_ff if cfg.mlp_kind == "swiglu" else cfg.d_ff
            reg("shared_mlp_in", d, fi)
            reg("shared_mlp_out", cfg.d_ff, d)
        return eng

    if cfg.attn_kind == "local_global":
        reg("attn_global_qkv", d, cfg.q_dim + 2 * cfg.kv_dim)
        reg("attn_global_out", cfg.q_dim, d)
        fi = 2 * cfg.d_ff if cfg.mlp_kind == "swiglu" else cfg.d_ff
        reg("mlp_global_in", d, fi)
        reg("mlp_global_out", cfg.d_ff, d)
        return eng

    reg("attn_qkv", *shapes["attn_qkv"])
    reg("attn_out", *shapes["attn_out"])
    if cfg.n_experts:
        if cfg.shared_expert:
            reg("moe_shared_in", d, 2 * cfg.d_ff)
            reg("moe_shared_out", cfg.d_ff, d)
    else:
        reg("mlp_in", *shapes["mlp_in"])
        reg("mlp_out", *shapes["mlp_out"])
    return eng


def _site_shapes(cfg: ModelConfig) -> dict[str, tuple[int, int]]:
    """(fan-in, fan-out) of each site of `layers.SITE_WEIGHTS`."""
    d, di, f = cfg.d_model, cfg.d_inner, cfg.d_ff
    return {
        "attn_qkv": (d, cfg.q_dim + 2 * cfg.kv_dim),
        "attn_out": (cfg.q_dim, d),
        "mlp_in": (d, 2 * f if cfg.mlp_kind == "swiglu" else f),
        "mlp_out": (f, d),
        "mamba_in": (d, 2 * di + 2 * cfg.ssm_groups * cfg.ssm_state
                     + cfg.n_ssm_heads),
        "mamba_out": (di, d),
    }


def prefill_step(
    params: Any, cfg: ModelConfig, tokens_or_inputs, state: dict
) -> tuple[jax.Array, dict]:
    """Process a prompt into fresh caches. Returns (last-token logits, state)."""
    inputs = (
        tokens_or_inputs
        if isinstance(tokens_or_inputs, dict)
        else {"tokens": tokens_or_inputs}
    )
    h, new_state, _, _ = forward(params, cfg, inputs, decode_state=state)
    with jax.named_scope("head"):
        last = h[:, -1:]
    logits = output_logits(params, cfg, last)
    return logits, new_state


def decode_step(
    params: Any,
    cfg: ModelConfig,
    token: jax.Array,        # [B, 1] int32
    state: dict,
    *,
    engine: ReuseEngine | None = None,
    reuse_cache: dict | None = None,
) -> tuple[jax.Array, dict, dict | None]:
    """One autoregressive step. Returns (logits [B,1,V], state, reuse_cache)."""
    h, new_state, new_rcache, _ = forward(
        params, cfg, {"tokens": token}, decode_state=state,
        reuse_engine=engine, reuse_cache=reuse_cache,
    )
    logits = output_logits(params, cfg, h)
    return logits, new_state, new_rcache


def jit_prefill(cfg: ModelConfig):
    """The compiled serving prefill: `(params, tokens, state) -> (logits,
    state)`."""
    return jax.jit(lambda p, toks, st: prefill_step(p, cfg, toks, st))


def jit_decode(cfg: ModelConfig, engine: ReuseEngine | None):
    """The compiled serving decode step: `(params, tokens, state, rcache) ->
    (logits, state, rcache)` with the serving state and the reuse cache
    DONATED, so XLA writes the new caches in place instead of allocating a
    copy per token. The engine's sites are baked into the trace; rebuild
    after a spec change."""

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def step(p, toks, st, rc):
        return decode_step(p, cfg, toks, st, engine=engine, reuse_cache=rc)

    return step


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def init_serve_state(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    return init_decode_state(cfg, batch, cache_len)
