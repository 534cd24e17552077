"""The comparison that decides `correct`.

A served request is a prompt and the tokens the timed path emitted for it.
The reference (the configuration's architecture module's `final_hidden`,
then the shared head of `reference.py`) runs once over the prompt with those
tokens appended (teacher-forced), and each served token is judged by how far
its reference logit lies below the reference's best logit at that position
(its gap: 0 where the served token is the reference's own greedy choice,
and the size of the miss otherwise), and by how many tokens the reference
ranks above it (its rank, 0 for the greedy choice). A run's numbers are the
widest and the mean of these over a sample of its finished requests, drawn
from the seed (`NUMBERS`); the cell's limits file names the ones it
compares.

The control puts the reference, computed at a lower precision, in the
program's place: at each position of the same prompts and tokens it reads the
gap of the token that the lower precision puts first.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chip import reference


@dataclasses.dataclass
class Served:
    prompt: np.ndarray   # [P] int32
    tokens: np.ndarray   # [n] int32, the tokens the timed path emitted
    finished: bool


def sample(requests: list[Served], count: int, seed: int) -> list[Served]:
    """`count` finished requests drawn from the seed, the longest always
    among them. A served token outside the vocabulary fails the check
    later, so every request is eligible."""
    done = [r for r in requests if r.finished and len(r.tokens)]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) % 2**64, 0xC4EC])
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.permutation(rest)[: max(count - 1, 0)].tolist()
    return [done[i] for i in [longest, *sorted(pick)]]


def _at(quant, prompt_len: int):
    """(bits, clip) applied from the first decode-fed position on."""
    return None if quant is None else (*quant, prompt_len)


def _sequence(r: Served) -> np.ndarray:
    # the last served token is never fed back, so it is not in the sequence
    return np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)


def codes(conf: dict, key: str | None):
    """(bits, clip) of the configuration's `precision[key]`, or None."""
    c = conf.get("precision", {}).get(key) if key else None
    return None if c is None else (int(c["bits"]), float(c["clip"]))


# The numbers a cell's limits file may compare: (reference, quantity, how).
# The reference is float32, either the model's plain arithmetic (None) or
# the same with the reuse-site inputs of decode steps rounded to the
# configuration's `precision.site_codes`; the quantity is each served
# token's gap or rank; how is the widest or the mean over the served tokens.
# A cell compares those whose control readings separate from its program
# readings.
NUMBERS = {
    "logit_gap": (None, "gap", "widest"),
    "logit_gap_coded": ("site_codes", "gap", "widest"),
    "logit_gap_coded_mean": ("site_codes", "gap", "mean"),
    "logit_rank_coded": ("site_codes", "rank", "widest"),
}


def statistic(values: np.ndarray, how: str) -> float:
    if not values.size:
        return float("inf")
    return float(np.max(values) if how == "widest" else np.mean(values))


def numbers_of(judged: dict, names) -> dict:
    """{name: number} from {reference key: {quantity: per-token values}}."""
    out = {}
    for n in names:
        key, qty, how = NUMBERS[n]
        out[n] = statistic(judged[key][qty], how)
    return out


def _wants_rank(names, key) -> bool:
    return any(NUMBERS[n][0] == key and NUMBERS[n][1] == "rank"
               for n in names)


def compared(conf: dict, names, family, spec, params, picked) -> dict:
    """{name: number} of the served tokens of `picked`, each reference run
    once. `family` is the configuration's architecture module and `spec`
    what its `spec_of` gives for the configuration."""
    judged = {key: served(family, spec, params, picked,
                          ref=codes(conf, key), ranks=_wants_rank(names, key))
              for key in {NUMBERS[n][0] for n in names}}
    return numbers_of(judged, names)


def _judge(params, h, best, tokens, ranks: bool) -> dict:
    got = reference.head_logits_at(params, h, tokens)
    out = {"gap": np.asarray(best - got, np.float64)}
    if ranks:
        out["rank"] = np.asarray(reference.head_count_above(
            params, h, tokens, got), np.float64)
    return out


def _join(parts: list[dict]) -> dict:
    if not parts:
        return {"gap": np.zeros(0), "rank": np.zeros(0)}
    return {q: np.concatenate([p[q] for p in parts]) for q in parts[0]}


def served(family, spec, params, requests: list[Served], ref=None,
           ranks: bool = False) -> dict:
    """{"gap": ..., "rank": ...} of each served token of every request
    against the reference (rank only when asked for). `ref` is the
    configuration's stated (bits, clip) of the reuse-site codes in decode
    steps. A token outside the vocabulary reads infinite."""
    vocab = reference.vocab_size(params)
    parts = []
    for r in requests:
        if np.any((r.tokens < 0) | (r.tokens >= vocab)):
            bad = np.array([np.inf])
            parts.append({"gap": bad, **({"rank": bad} if ranks else {})})
            continue
        p = len(r.prompt)
        h = family.final_hidden(params, spec, _sequence(r),
                                quant=_at(ref, p))[p - 1:]
        best, _ = reference.head_max_argmax(params, h)
        parts.append(_judge(params, h, best, r.tokens, ranks))
    return _join(parts)


def control(family, spec, params, requests: list[Served], quant, ref=None,
            ranks: bool = False) -> dict:
    """The gap (and rank) of the token the reference with `quant` codes at
    the reuse sites puts first, at each position of the same prompts and
    served tokens."""
    parts = []
    for r in requests:
        p = len(r.prompt)
        seq = _sequence(r)
        h = family.final_hidden(params, spec, seq, quant=_at(ref, p))[p - 1:]
        best, _ = reference.head_max_argmax(params, h)
        hq = family.final_hidden(params, spec, seq,
                                 quant=_at(quant, p))[p - 1:]
        _, pick = reference.head_max_argmax(params, hq)
        parts.append(_judge(params, h, best, pick, ranks))
    return _join(parts)
