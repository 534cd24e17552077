"""The pattern stack (nemotron-h) against a plain float32 reference.

The reference runs one request at a time and one layer at a time, at
HIGHEST matmul precision, with Mamba2 as the per-token recurrence of its
definition (arXiv:2405.21060; the published `RMSNormGated` with
`norm_before_gate=False`):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_g(t)ᵀ,   y_t = h_t C_g(t) + D x_t
    out = W_out · groupnorm(y ⊙ silu(z))

where head h reads group h // (H / G) of B and C. Attention has no position
encoding; the MLP is ungated squared ReLU. It reads the program's parameter
tree and nothing else of the program. `quant` rounds the input of each
linear reuse site at decode positions to the engine's int8 codes (scale
0.05), which is what a reuse engine serves: its output is the dense GEMM of
the rounded input.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_decode_state, init_params, output_logits
from repro.models import ssm
from repro.serve.serve_step import build_reuse_engine, decode_step

HI = jax.lax.Precision.HIGHEST
CODE_STEP, CODE_MAX = 0.05, 127
PROMPT, NEW, BATCH = 12, 4, 2


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _round(x, rows_from):
    """Int8 codes at the positions from `rows_from` on; None: no rounding."""
    if rows_from is None:
        return x
    q = jnp.clip(jnp.round(x / CODE_STEP), -CODE_MAX, CODE_MAX) * CODE_STEP
    rows = jnp.arange(x.shape[0])[:, None] >= rows_from
    return jnp.where(rows, q, x)


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=HI)


def ref_mamba(p, cfg, x, *, quant=None, groups=None, gate_first=True):
    """One Mamba2 mixer over one request's [T, d], without the residual.
    `groups` and `gate_first` give the variants the program must not be."""
    di, st, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, \
        cfg.ssm_head_dim
    g = cfg.ssm_groups
    gv = groups or g
    t = x.shape[0]
    zxbcdt = _mm(_round(_rms(x, p["norm"]["scale"], cfg.norm_eps), quant),
                 p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * st], -1)
    w = p["conv_w"].astype(jnp.float32)
    k = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + t] * w[i] for i in range(k)) + p["conv_b"]
    xs, b, c = jnp.split(jax.nn.silu(conv), [di, di + g * st], -1)
    xs = xs.reshape(t, nh, hd)
    b = b.reshape(t, g, st)[:, :gv]
    c = c.reshape(t, g, st)[:, :gv]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    grp = jnp.arange(nh) // (nh // gv)          # head h reads group h // (H/G)
    h = jnp.zeros((nh, hd, st))
    ys = []
    for i in range(t):
        h = (jnp.exp(dt[i] * a)[:, None, None] * h
             + dt[i][:, None, None] * xs[i][:, :, None] * b[i][grp][:, None, :])
        ys.append(jnp.einsum("hps,hs->hp", h, c[i][grp], precision=HI)
                  + p["D"][:, None] * xs[i])
    y = jnp.stack(ys).reshape(t, di)
    scale = p["out_norm"]["scale"]
    if gate_first:
        y = y * jax.nn.silu(z)
    y = y.reshape(t, g, di // g)
    y = _rms(y, 0.0, cfg.norm_eps).reshape(t, di) * (1.0 + scale)
    if not gate_first:
        y = y * jax.nn.silu(z)
    return _mm(_round(y, quant), p["out_proj"])


def ref_attention(p, cfg, x, quant):
    t = x.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = _mm(_round(_rms(x, p["norm"]["scale"], cfg.norm_eps), quant),
              p["wqkv"])
    q, k, v = jnp.split(qkv, [nh * hd, nh * hd + nkv * hd], -1)
    q = q.reshape(t, nkv, nh // nkv, hd)    # query head h reads kv h // rep
    k, v = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) / math.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(_round(o.reshape(t, nh * hd), quant), p["wo"])


def ref_mlp(p, cfg, x, quant):
    hi = _mm(_round(_rms(x, p["norm"]["scale"], cfg.norm_eps), quant),
             p["wi"])
    return _mm(_round(jnp.square(jnp.maximum(hi, 0.0)), quant), p["wo"])


def ref_logits(params, cfg, tokens, quant=None, **variant):
    """Logits [T, V] of one request through every layer of the pattern."""
    x = params["embed"][tokens].astype(jnp.float32)
    seen = {}
    for letter in cfg.layer_pattern:
        i = seen.get(letter, 0)
        seen[letter] = i + 1
        group = {"M": "mamba", "-": "mlp", "*": "attn"}[letter]
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32),
                         params["blocks"][group])
        if letter == "M":
            x = x + ref_mamba(p, cfg, x, quant=quant, **variant)
        elif letter == "-":
            x = x + ref_mlp(p, cfg, x, quant)
        else:
            x = x + ref_attention(p, cfg, x, quant)
    x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    return _mm(x, params["lm_head"])


def _spread(params, key):
    """The program's initial norm scales, A_log, dt_bias, D and conv bias
    are constants; draw them, so that the comparison sees each one."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        if path[-1].key in ("scale", "A_log", "dt_bias", "D", "conv_b"):
            leaf = leaf + 0.5 * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree.unflatten(tree, out)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("nemotron-h-47b").reduced()
    assert set(cfg.layer_pattern) == {"M", "-", "*"} and cfg.ssm_groups > 1
    params = _spread(init_params(cfg, jax.random.PRNGKey(3)),
                     jax.random.PRNGKey(4))
    return cfg, params


def _tokens(cfg, n):
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, n)), jnp.int32)


# ------------------------------------------------------------------ Mamba2

def _mixer(model, layer=1):
    cfg, params = model
    p = jax.tree.map(lambda a: a[layer], params["blocks"]["mamba"])
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(BATCH, PROMPT + NEW, cfg.d_model)), jnp.float32)
    return cfg, p, x


def _mixer_program(cfg, p, x, streamed: bool):
    st = ssm.init_mamba2_state(cfg, x.shape[0])
    if not streamed:
        return ssm.mamba2_forward(p, cfg, x, st)[0]
    out, st = ssm.mamba2_forward(p, cfg, x[:, :PROMPT], st)
    outs = [out]
    for t in range(PROMPT, x.shape[1]):
        o, st = ssm.mamba2_forward(p, cfg, x[:, t:t + 1], st)
        outs.append(o)
    return jnp.concatenate(outs, axis=1)


# float32 on both sides; the program's chunked scan and fused sums reorder
# float32 additions, which moves an output of size ~1 by about 1e-6
MIXER_TOL = 1e-4


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["one_shot", "prompt_then_tokens"])
def test_grouped_mamba2_equals_recurrence(model, streamed):
    cfg, p, x = _mixer(model)
    got = np.asarray(_mixer_program(cfg, p, x, streamed))
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref_mamba(p, cfg, xb)) for xb in x])
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=MIXER_TOL, rtol=MIXER_TOL)


@pytest.mark.parametrize("variant", [dict(groups=1), dict(gate_first=False)],
                         ids=["one_group", "norm_before_gate"])
def test_mamba2_variants_fail_the_comparison(model, variant):
    """A mixer with one group for B and C, or with the norm before the
    gate, lies far outside the tolerance the program meets."""
    cfg, p, x = _mixer(model)
    got = np.asarray(_mixer_program(cfg, p, x, streamed=False))
    with jax.default_matmul_precision("highest"):
        other = np.stack([np.asarray(ref_mamba(p, cfg, xb, **variant))
                          for xb in x])
    assert np.abs(got - other).max() > 100 * MIXER_TOL


# ------------------------------------------------------------ whole model

def _served_logits(cfg, params, toks, engine):
    """Prefill the prompt, then decode NEW tokens teacher-forced through
    the caches: the logits at positions PROMPT-1 .. PROMPT+NEW-1."""
    state = init_decode_state(cfg, BATCH, PROMPT + NEW + 4)
    h, state, _, _ = forward(params, cfg, {"tokens": toks[:, :PROMPT]},
                             decode_state=state)
    out = [output_logits(params, cfg, h[:, -1:])]
    rcache = engine.init_cache(BATCH) if engine is not None else None
    step = jax.jit(lambda p, t, s, r: decode_step(
        p, cfg, t, s, engine=engine, reuse_cache=r))
    for t in range(PROMPT, PROMPT + NEW):
        logits, state, rcache = step(params, toks[:, t:t + 1], state, rcache)
        out.append(logits)
    return np.asarray(jnp.concatenate(out, axis=1))


def _reference(cfg, params, toks, quant, **variant):
    with jax.default_matmul_precision("highest"):
        return np.stack([
            np.asarray(ref_logits(params, cfg, tb, quant=quant, **variant))
            [PROMPT - 1:] for tb in toks[:, :PROMPT + NEW]])


# reuse off: float32 both sides, summation order only. Reuse on: both
# round the same site inputs to the same codes, but an input the two sides
# compute 1e-6 apart can land on either side of a code boundary, which
# moves that site's output by one code step (0.05) times a weight row, so
# the bound leaves room for a few such codes
@pytest.mark.parametrize("reuse,tol", [(False, 2e-4), (True, 2e-2)],
                         ids=["reuse_off", "reuse_on"])
def test_hybrid_prefill_decode_matches_reference(model, reuse, tol):
    cfg, params = model
    toks = _tokens(cfg, PROMPT + NEW)
    engine = build_reuse_engine(cfg, impl="pallas") if reuse else None
    got = _served_logits(cfg, params, toks, engine)
    quant = PROMPT if reuse else None
    want = _reference(cfg, params, toks, quant)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)
    for variant in (dict(groups=1), dict(gate_first=False)):
        other = _reference(cfg, params, toks, quant, **variant)
        assert np.abs(got - other).max() > 10 * tol, variant
    if reuse:
        # unrounded, the reference misses the served codes by far more
        plain = _reference(cfg, params, toks, None)
        assert np.abs(got - plain).max() > 3 * tol


def test_pattern_layers_keep_their_kinds():
    """Each kind's parameters and state are stacked by kind, in the count
    the pattern gives; a depth cut keeps the first layers' letters, and a
    pattern override of the same length picks other layers."""
    full = get_config("nemotron-h-47b")
    kinds = ("M", "-", "*")
    assert [full.kind_layers(c) for c in kinds] == [45, 48, 5]
    assert full.with_layers(19).layer_pattern == full.layer_pattern[:19]
    stage = dataclasses.replace(full.with_layers(9),
                                layer_pattern=full.layer_pattern[10:19])
    assert [stage.kind_layers(c) for c in kinds] == [4, 4, 1]
    for bad in ("M-X", "M-"):
        with pytest.raises(ValueError, match="layer_pattern"):
            dataclasses.replace(stage, layer_pattern=bad)
    cfg = full.reduced()
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: init_decode_state(cfg, BATCH, 16))
    for letter, group in (("M", "mamba"), ("-", "mlp"), ("*", "attn")):
        lead = {a.shape[0] for a in jax.tree.leaves(params["blocks"][group])}
        assert lead == {cfg.kind_layers(letter)}
    assert set(state["blocks"]) == {"mamba", "attn"}
    g, st = cfg.ssm_groups, cfg.ssm_state
    in_proj = params["blocks"]["mamba"]["in_proj"].shape
    assert in_proj[-1] == 2 * cfg.d_inner + 2 * g * st + cfg.n_ssm_heads
