"""Seeded random weights, made by the benchmark and handed to the program.

The weights come from `--seed` alone, in one jitted call on the device, in
the type they are served in, laid out as the configuration's architecture
module says (`arch/<model_type>.py`, `layout`): as the program loads them, a
checkpoint format like any loader's. The reference reads the same arrays;
neither the weights nor the layout come from the program's own initialiser.
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


def make_params(layout: dict, seed: int) -> dict:
    """Every weight of `layout` ({path: (shape, dtype, std)}, std None for
    a norm weight), drawn from `seed` on the default device."""
    spec = tuple((p, s, jnp.dtype(dt).name, std)
                 for p, (s, dt, std) in sorted(layout.items()))
    return _draw(spec, seed_key(seed))
