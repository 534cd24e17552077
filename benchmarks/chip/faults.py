"""Faults planted in the timed path, to show that the check catches them.

Each wraps a built `system.System` in place: the decode step or the sampler
is replaced by one that breaks a guarantee of serving. The benchmark's own
runs never plant one; `calibrate.py --fault <name>` reads the compared
numbers under a fault at a cell's own size, and `tests/test_faults.py` sees
`correct` come out false at a CPU test's size. The cells run on one chip,
so there is no exchange between chips to leave out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def state_unchanged(sut) -> None:
    """The decode step hands back the serving state it was given."""
    step = sut.decode

    def decode(p, tok, st, rc):
        keep = jax.tree.map(jnp.copy, st)
        logits, _, rc = step(p, tok, st, rc)
        return logits, keep, rc

    sut.decode = decode


def half_batch(sut) -> None:
    """The second half of the lanes is never computed: it gets the first
    half's logits."""
    step = sut.decode

    def decode(p, tok, st, rc):
        logits, st, rc = step(p, tok, st, rc)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), st, rc

    sut.decode = decode


def token_altered(sut) -> None:
    """One token of one request changes where the sampler produces it: lane
    3's token at the sixth sampling after the fault is planted, a decode
    step of the first wave served (warm-up included)."""
    sample = sut.sample
    calls = {"n": 0}

    def altered(logits):
        tok = sample(logits)
        calls["n"] += 1
        if calls["n"] == 6:
            tok = tok.at[3].set((tok[3] + 1) % logits.shape[-1])
        return tok

    sut.sample = altered


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}
