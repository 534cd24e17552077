"""Seeded random weights, made by the benchmark and handed to the program.

The weights come from `--seed` alone, in one jitted call on the device, in
the type they are served in. They are laid out as the program loads them (a
checkpoint format, like any loader's): the stacked per-layer matrices of a
dense decoder, q|k|v fused in one matrix, gate|up fused in one matrix for a
gated MLP, and each norm weight stored as its offset from 1. The reference
(`reference.py`) reads the same arrays through that layout; neither the
weights nor the layout come from the program's own initialiser.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1   # norm weights are 1 + NORM_SPREAD * N(0, 1)


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding all 64 bits of `seed` (`jax.random.key`
    keeps only the low 32 without x64)."""
    s = int(seed) % 2**64
    words = np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def layout(model: dict) -> dict:
    """{path: (shape, dtype, std)} of every parameter; std None marks a
    norm weight (stored as an offset from 1)."""
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


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *heads, last = path.split("/")
        node = tree
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnums=0)
def _draw(spec: tuple, key: jax.Array) -> dict:
    keys = jax.random.split(key, len(spec))
    flat = {}
    for k, (path, shape, dtype, std) in zip(keys, spec):
        z = jax.random.normal(k, shape, jnp.float32)
        z = z * NORM_SPREAD if std is None else z * std
        flat[path] = z.astype(dtype)
    return _nest(flat)


def make_params(model: dict, seed: int) -> dict:
    """Every weight of `model`, drawn from `seed` on the default device."""
    spec = tuple((p, s, jnp.dtype(dt).name, std)
                 for p, (s, dt, std) in sorted(layout(model).items()))
    return _draw(spec, seed_key(seed))
