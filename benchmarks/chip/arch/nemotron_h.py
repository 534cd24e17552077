"""Nemotron-H (`model_type` nemotron_h): a hybrid stack laid out by
`hybrid_override_pattern`, one letter a layer, each layer one mixer with its
own pre-RMSNorm and residual:

    M   x + Wout . groupnorm(y * silu(z)),  y the Mamba2 scan below
    -   x + Wdown . relu(norm(x) . Wup)^2
    *   x + Wo . attn(q, k, v),  [q | k | v] = norm(x) . Wqkv   (GQA,
        causal, no position encoding)

The Mamba2 mixer (arXiv:2405.21060, the published `NemotronHMamba2Mixer`):
[z | xBC | dt] = norm(x) . Win; xBC passes a causal depthwise conv of width
`conv_kernel` with bias and SiLU and splits into x (H heads of P), B and C
(G groups of N each); dt = softplus(dt + dt_bias), unclamped; then, per
token t and head h, which reads group g = h // (H / G):

    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_g(t)^T,   A = -exp(A_log)
    y_t = s_t C_g(t) + D x_t

and the gated RMSNorm multiplies by silu(z) first and then normalises each
of the G groups of channels on its own (`norm_before_gate=False`).

Weights are laid out as the program loads them: each kind's layers stacked
on their own (`blocks/mamba`, `blocks/mlp`, `blocks/attn`), q|k|v fused, and
each norm weight stored as its offset from 1. The reference runs one request
and one layer at a time in float32, the scan as the per-token recurrence of
its definition. The six linear sites (mamba_in, mamba_out, mlp_in, mlp_out,
attn_qkv, attn_out) are the program's reuse sites; `quant` rounds their
inputs.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chip.reference import HI, mm, rms_norm, round_codes
from chip.work import BF16, F32, Job

KINDS = {"M": "mamba", "-": "mlp", "*": "attn"}
# the random draws of the Mamba2 parameters that have no fan-in
A_LOG_STD, DT_BIAS_STD, D_STD, CONV_BIAS_STD = 1.0, 1.0, 1.0, 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the layout, the reference and the work counts read."""

    pattern: str
    d_model: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    ssm_heads: int
    ssm_head_dim: int
    groups: int
    state: int
    conv: int
    eps: float

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        return 2 * self.d_inner + 2 * self.groups * self.state + self.ssm_heads

    def layers(self, letter: str) -> int:
        return self.pattern.count(letter)


def spec_of(model: dict) -> Sizes:
    return Sizes(
        pattern=model["hybrid_override_pattern"],
        d_model=model["hidden_size"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"],
        head_dim=model["attention_head_dim"],
        ffn=model["intermediate_size"],
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"], groups=model["n_groups"],
        state=model["ssm_state_size"], conv=model["conv_kernel"],
        eps=float(model["rms_norm_eps"]))


def layout(model: dict) -> dict:
    z = spec_of(model)
    d, di, q = z.d_model, z.d_inner, z.heads * z.head_dim
    kv = z.kv_heads * z.head_dim
    wt = jnp.dtype(model["torch_dtype"])
    f32 = jnp.dtype(jnp.float32)
    nm, nf, na = z.layers("M"), z.layers("-"), z.layers("*")
    out = {"embed": ((z.vocab, d), wt, d ** -0.5),
           "final_norm/scale": ((d,), f32, None)}
    if nm:
        out.update({
            "blocks/mamba/norm/scale": ((nm, d), f32, None),
            "blocks/mamba/in_proj": ((nm, d, z.in_width), wt, d ** -0.5),
            "blocks/mamba/conv_w": ((nm, z.conv, z.conv_channels), wt,
                                    z.conv ** -0.5),
            "blocks/mamba/conv_b": ((nm, z.conv_channels), f32,
                                    CONV_BIAS_STD),
            "blocks/mamba/A_log": ((nm, z.ssm_heads), f32, A_LOG_STD),
            "blocks/mamba/dt_bias": ((nm, z.ssm_heads), f32, DT_BIAS_STD),
            "blocks/mamba/D": ((nm, z.ssm_heads), f32, D_STD),
            "blocks/mamba/out_norm/scale": ((nm, di), f32, None),
            "blocks/mamba/out_proj": ((nm, di, d), wt, di ** -0.5),
        })
    if nf:
        out.update({
            "blocks/mlp/norm/scale": ((nf, d), f32, None),
            "blocks/mlp/wi": ((nf, d, z.ffn), wt, d ** -0.5),
            "blocks/mlp/wo": ((nf, z.ffn, d), wt, z.ffn ** -0.5),
        })
    if na:
        out.update({
            "blocks/attn/norm/scale": ((na, d), f32, None),
            "blocks/attn/wqkv": ((na, d, q + 2 * kv), wt, d ** -0.5),
            "blocks/attn/wo": ((na, q, d), wt, q ** -0.5),
        })
    if not model["tie_word_embeddings"]:
        out["lm_head"] = ((d, z.vocab), wt, d ** -0.5)
    return out


# ------------------------------------------------------------- reference

@functools.partial(jax.jit, static_argnames=("spec", "quant", "state_from"))
def mamba(x, blocks, layer, *, spec, quant, state_from=None):
    """Mamba2 layer `layer` of the stacked `blocks` over one request's
    [T, d], with its residual. With `state_from`, the state carried on from
    each position from that one on is rounded to bfloat16, as a program
    that keeps it in bfloat16 between steps stores it."""
    t = x.shape[0]
    w = jax.tree.map(lambda a: a[layer], blocks)
    di, g, n = spec.d_inner, spec.groups, spec.state
    h, p = spec.ssm_heads, spec.ssm_head_dim
    zxbcdt = mm(round_codes(rms_norm(x, w["norm"]["scale"], spec.eps), quant),
                w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    cw = w["conv_w"].astype(jnp.float32)
    pad = jnp.concatenate([jnp.zeros((spec.conv - 1, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + t] * cw[i] for i in range(spec.conv)) + w["conv_b"]
    xs, b, c = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                      # [T, H]
    a = -jnp.exp(w["A_log"])
    group = jnp.arange(h) // (h // g)     # head h reads group h // (H / G)

    def token(s, ins):
        x_t, b_t, c_t, dt_t, pos = ins    # [H, P], [G, N], [G, N], [H], []
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, c_t[group], precision=HI)
        if state_from is not None:
            s = jnp.where(pos >= state_from,
                          s.astype(jnp.bfloat16).astype(jnp.float32), s)
        return s, y + w["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), jnp.float32),
                        (xs.reshape(t, h, p), b.reshape(t, g, n),
                         c.reshape(t, g, n), dt, jnp.arange(t)))
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + spec.eps)
    y = y.reshape(t, di) * (1.0 + w["out_norm"]["scale"])
    return x + mm(round_codes(y, quant), w["out_proj"])


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def mlp(x, blocks, layer, *, spec, quant):
    w = jax.tree.map(lambda a: a[layer], blocks)
    hi = mm(round_codes(rms_norm(x, w["norm"]["scale"], spec.eps), quant),
            w["wi"])
    return x + mm(round_codes(jnp.square(jnp.maximum(hi, 0.0)), quant),
                  w["wo"])


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def attention(x, blocks, layer, *, spec, quant):
    w = jax.tree.map(lambda a: a[layer], blocks)
    t, nh, nkv, hd = x.shape[0], spec.heads, spec.kv_heads, spec.head_dim
    qkv = mm(round_codes(rms_norm(x, w["norm"]["scale"], spec.eps), quant),
             w["wqkv"])
    q, k, v = jnp.split(qkv, [nh * hd, nh * hd + nkv * hd], axis=-1)
    qg = q.reshape(t, nkv, nh // nkv, hd)   # query head h reads kv h // rep
    k, v = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", qg, k, precision=HI) / math.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(t, nh * hd)
    return x + mm(round_codes(o, quant), w["wo"])


LAYER = {"M": mamba, "-": mlp, "*": attention}


@functools.partial(jax.jit, static_argnames=("spec",))
def _final(x, scale, *, spec):
    return rms_norm(x, scale, spec.eps)


def final_hidden(params, spec, tokens, quant=None,
                 state_from=None) -> jax.Array:
    """The float32 reference through the final norm. `state_from`: round
    the SSM state to bfloat16 from that position on (`mamba`)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    seen = dict.fromkeys(KINDS, 0)
    for letter in spec.pattern:
        extra = {"state_from": state_from} if letter == "M" else {}
        x = LAYER[letter](x, params["blocks"][KINDS[letter]],
                          seen[letter], spec=spec, quant=quant, **extra)
        seen[letter] += 1
    return _final(x, params["final_norm"]["scale"], spec=spec)


# ------------------------------------------------------------- work

@dataclasses.dataclass(frozen=True)
class HybridShape:
    """The operations a Nemotron-H stack needs: two per weight of its
    linear layers and head, attention over the valid context, and the
    Mamba2 scan's own work."""

    sizes: Sizes

    def layer_params(self) -> int:
        z = self.sizes
        d, q, kv = z.d_model, z.heads * z.head_dim, z.kv_heads * z.head_dim
        return (z.layers("M") * (d * z.in_width + z.d_inner * d)
                + z.layers("-") * 2 * d * z.ffn
                + z.layers("*") * (d * (q + 2 * kv) + q * d))

    def attention_flops(self, keys: int) -> float:
        z = self.sizes
        return 4.0 * z.layers("*") * z.heads * z.head_dim * keys

    def ssm_flops(self) -> float:
        """One token through every Mamba2 layer: the conv's multiply-adds,
        and per state element the decay, the input's outer product and its
        sum (3) and the readout against C (2)."""
        z = self.sizes
        return z.layers("M") * (2.0 * z.conv * z.conv_channels
                                + 5.0 * z.d_inner * z.state)

    def decode_flops(self, batch: int, position: int) -> float:
        z = self.sizes
        return batch * (2.0 * (self.layer_params() + z.d_model * z.vocab)
                        + self.attention_flops(position + 1)
                        + self.ssm_flops())

    def prefill_flops(self, batch: int, length: int) -> float:
        z = self.sizes
        keys = length * (length + 1) / 2
        return batch * (2.0 * self.layer_params() * length
                        + 2.0 * z.d_model * z.vocab
                        + self.attention_flops(1) * keys
                        + self.ssm_flops() * length)


def shape(model: dict) -> HybridShape:
    return HybridShape(spec_of(model))


def ssm_update_job(model: dict, batch: int) -> Job:
    """One decode step's SSM update of every Mamba2 layer, all but its two
    projections, for `batch` rows: read and write the float32 SSM state and
    the bf16 conv state; read the conv weights and bias, the small per-head
    and norm parameters, the layer input and the in-projection's output
    (bf16); write the normalised input and the gated, normalised output
    (bf16). Operations: the pre-norm, `HybridShape.ssm_flops`, the gate and
    the group norm."""
    z = spec_of(model)
    d, di, c = z.d_model, z.d_inner, z.conv_channels
    state = z.ssm_heads * z.ssm_head_dim * z.state
    per_layer = (
        2 * F32 * batch * state
        + 2 * BF16 * batch * (z.conv - 1) * c
        + BF16 * z.conv * c + F32 * c
        + 3 * F32 * z.ssm_heads + F32 * (d + di)
        + BF16 * batch * (d + z.in_width)
        + BF16 * batch * (d + di))
    flops = batch * (HybridShape(z).ssm_flops()
                     + z.layers("M") * (4.0 * d + 8.0 * di))
    return Job(flops=flops, bytes=float(z.layers("M") * per_layer))


# ------------------------------------------------------------- program

program_fields = {
    "num_hidden_layers": ("n_layers", int),
    "hybrid_override_pattern": ("layer_pattern", str),
    "hidden_size": ("d_model", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "attention_head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "vocab_size": ("vocab", int),
    "rms_norm_eps": ("norm_eps", float),
    "layer_norm_epsilon": ("norm_eps", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
    "attention_bias": ("qkv_bias", bool),
    "mlp_hidden_act": ("mlp_kind", {"relu2": "relu2"}.__getitem__),
    "mamba_num_heads": ("n_ssm_heads", int),
    "mamba_head_dim": ("ssm_head_dim", int),
    "n_groups": ("ssm_groups", int),
    "ssm_state_size": ("ssm_state", int),
    "torch_dtype": ("param_dtype", str),
}


def check_program(cfg) -> dict:
    """A pattern stack of Mamba2 mixers, full attention without a position
    encoding, and no experts."""
    got = (bool(cfg.layer_pattern), cfg.ssm_kind, cfg.attn_kind, cfg.rope,
           cfg.n_experts)
    want = (True, "mamba2", "full", "none", 0)
    return {} if got == want else {"block": (got, want)}
