"""A dense grouped-query decoder: every layer is attention, then an MLP.

Weights are laid out as the program loads them (a checkpoint format, like
any loader's): the stacked per-layer matrices, q|k|v fused in one matrix,
gate|up fused in one matrix for a gated MLP, and each norm weight stored as
its offset from 1. The reference follows the published layer equations of
the configuration's `model` group:

    h   = x + Wo . attn(rope(norm_qk(q)), rope(norm_qk(k)), v),
          [q | k | v] = norm(x) . Wqkv        (grouped-query, causal)
    out = h + Wdown . act(norm(h) . Wup)      (silu(gate) * up, or relu^2)
    logits = norm(out_L) . Whead              (Whead = embed^T when tied)

with RMSNorm and rotate-half RoPE over the whole head, one request at a time
and one layer at a time: each layer is widened to float32 only while it
runs. The four linear sites of a layer (attn_qkv, attn_out, mlp_in,
mlp_out) are the program's reuse sites; `quant` rounds their inputs.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chip.reference import HI, mm, rms_norm, rope, round_codes


def layout(model: dict) -> dict:
    d, v, n = model["hidden_size"], model["vocab_size"], \
        model["num_hidden_layers"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    f = model["intermediate_size"]
    wide = 2 * f if model["hidden_act"] == "silu" else f
    wt = jnp.dtype(model["torch_dtype"])
    f32 = jnp.dtype(jnp.float32)
    out = {
        "embed": ((v, d), wt, d ** -0.5),
        "blocks/attn/wqkv": ((n, d, q + 2 * kv), wt, d ** -0.5),
        "blocks/attn/wo": ((n, q, d), wt, q ** -0.5),
        "blocks/attn/norm/scale": ((n, d), f32, None),
        "blocks/mlp/wi": ((n, d, wide), wt, d ** -0.5),
        "blocks/mlp/wo": ((n, f, d), wt, f ** -0.5),
        "blocks/mlp/norm/scale": ((n, d), f32, None),
        "final_norm/scale": ((d,), f32, None),
    }
    if model["qk_norm"]:
        hd = model["head_dim"]
        out["blocks/attn/q_norm/scale"] = ((n, hd), f32, None)
        out["blocks/attn/k_norm/scale"] = ((n, hd), f32, None)
    if not model["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), wt, d ** -0.5)
    return out


def spec_of(model: dict) -> tuple:
    return (
        model["hidden_size"], model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"],
        model["hidden_act"], bool(model["qk_norm"]),
        float(model["rope_theta"]), float(model["rms_norm_eps"]),
    )


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def block(x, blocks, layer, *, spec, quant):
    """Layer `layer` of the stacked `blocks` over one request's [T, d]."""
    d, nh, nkv, hd, act, qk_norm, theta, eps = spec
    w = jax.tree.map(lambda a: a[layer], blocks)
    t = x.shape[0]
    pos = jnp.arange(t)

    a = w["attn"]
    qkv = mm(round_codes(rms_norm(x, a["norm"]["scale"], eps), quant),
             a["wqkv"])
    q, k, v = jnp.split(qkv, [nh * hd, nh * hd + nkv * hd], axis=-1)
    q = q.reshape(t, nh, hd)
    k = k.reshape(t, nkv, hd)
    v = v.reshape(t, nkv, hd)
    if qk_norm:
        q = rms_norm(q, a["q_norm"]["scale"], eps)
        k = rms_norm(k, a["k_norm"]["scale"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    rep = nh // nkv
    qg = q.reshape(t, nkv, rep, hd)     # query head h reads kv head h // rep
    s = jnp.einsum("qgrd,kgd->grqk", qg, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * hd)
    x = x + mm(round_codes(o, quant), a["wo"])

    m = w["mlp"]
    hi = mm(round_codes(rms_norm(x, m["norm"]["scale"], eps), quant),
            m["wi"])
    if act == "silu":
        gate, up = jnp.split(hi, 2, axis=-1)
        hi = jax.nn.silu(gate) * up
    elif act == "relu2":
        hi = jnp.square(jnp.maximum(hi, 0.0))
    else:
        raise ValueError(f"unknown hidden_act {act!r}")
    return x + mm(round_codes(hi, quant), m["wo"])


@functools.partial(jax.jit, static_argnames=("spec",))
def _final(x, scale, *, spec):
    return rms_norm(x, scale, spec[7])


def final_hidden(params, spec, tokens, quant=None) -> jax.Array:
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for layer in range(params["blocks"]["attn"]["wqkv"].shape[0]):
        x = block(x, params["blocks"], layer, spec=spec, quant=quant)
    return _final(x, params["final_norm"]["scale"], spec=spec)


@dataclasses.dataclass(frozen=True)
class DenseShape:
    """The sizes of a dense decoder that the model FLOP count needs."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    gated: bool

    def layer_params(self) -> int:
        """Weights one token multiplies through in the layers."""
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        attn = d * (q + 2 * kv) + q * d
        mlp = (3 if self.gated else 2) * d * self.ffn
        return self.layers * (attn + mlp)

    def linear_params(self) -> int:
        """Weights a token multiplies through, the output head included."""
        return self.layer_params() + self.d_model * self.vocab

    def attention_flops(self, keys: int) -> float:
        """QK^T and PV for one query over `keys` positions, all layers."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys

    def decode_flops(self, batch: int, position: int) -> float:
        """One decode step of `batch` rows whose new token sits at
        `position` (0-based), so it attends over position + 1 keys."""
        return batch * (2.0 * self.linear_params()
                        + self.attention_flops(position + 1))

    def prefill_flops(self, batch: int, length: int) -> float:
        """A causal prefill of `batch` prompts of `length` tokens, with
        logits for each prompt's last position only."""
        keys = length * (length + 1) / 2
        return batch * (2.0 * self.layer_params() * length
                        + 2.0 * self.d_model * self.vocab
                        + self.attention_flops(1) * keys)


def shape(model: dict) -> DenseShape:
    return DenseShape(
        layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        ffn=model["intermediate_size"], vocab=model["vocab_size"],
        gated=model["hidden_act"] == "silu")


_ACT = {"silu": "swiglu", "relu2": "relu2"}

program_fields = {
    "num_hidden_layers": ("n_layers", int),
    "hidden_size": ("d_model", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "vocab_size": ("vocab", int),
    "rms_norm_eps": ("norm_eps", float),
    "rope_theta": ("rope_theta", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
    "qk_norm": ("qk_norm", bool),
    "attention_bias": ("qkv_bias", bool),
    "hidden_act": ("mlp_kind", _ACT.__getitem__),
    "torch_dtype": ("param_dtype", str),
}


def check_program(cfg) -> dict:
    """Full attention with RoPE on every layer, and no experts."""
    if cfg.attn_kind != "full" or cfg.rope != "rope" or cfg.n_experts:
        return {"block": ((cfg.attn_kind, cfg.rope, cfg.n_experts),
                          ("full", "rope", 0))}
    return {}
