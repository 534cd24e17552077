"""The served prefill and decode, through the KV cache and the reuse cache,
against the float32 reference, at a size a CPU holds."""

import numpy as np

from chip import check, reference, weights
from chip.tests.conftest import run_tiny, tiny_cell


def test_reuse_off_matches_reference_to_rounding():
    r = run_tiny(7, config="tiny-noreuse")
    assert r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] < 1e-3


def test_reuse_on_served_tokens_within_limit():
    r = run_tiny(8)
    assert r["correct"], r["check"]
    # float32 served codes equal the reference's: agreement to rounding
    assert r["check"]["logit_gap_coded"]["value"] < 1e-3
    assert r["check"]["compilations_in_window"]["value"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p50_ms", "itl_p95_ms",
                                 "setup_s"}


def test_reference_greedy_tokens_have_zero_gap():
    # the reference's own greedy continuation lies 0 below its best
    cell = tiny_cell()
    model, family = cell.conf["model"], cell.family
    spec = family.spec_of(model)
    params = weights.make_params(family.layout(model), 5)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    seq = list(prompt)
    for _ in range(4):
        h = family.final_hidden(params, spec, seq)
        _, arg = reference.head_max_argmax(params, h[-1:])
        seq.append(int(arg[0]))
    served = check.Served(prompt=np.array(prompt), tokens=np.array(seq[8:]),
                          finished=True)
    judged = check.served(family, spec, params, [served], ranks=True)
    assert np.max(judged["gap"]) == 0.0
    assert np.max(judged["rank"]) == 0.0


def test_rank_counts_the_tokens_the_reference_prefers():
    cell = tiny_cell()
    model, family = cell.conf["model"], cell.family
    params = weights.make_params(family.layout(model), 6)
    prompt = np.array([2, 7, 1, 8, 2, 8, 1, 8])
    h = family.final_hidden(params, family.spec_of(model), prompt)[-1:]
    w = np.asarray(params["embed"], np.float64)      # tied head [V, d]
    logits = np.asarray(h, np.float64) @ w.T
    order = np.argsort(-logits[0])
    for rank in (0, 1, 5, 200, len(order) - 1):
        tok = np.array([order[rank]])
        got = reference.head_logits_at(params, h, tok)
        assert int(reference.head_count_above(params, h, tok,
                                              got)[0]) == rank
        # in blocks of 100 over 512 ids: the last block overlaps the one
        # before it, and each id still counts once
        assert int(reference._count_above(h, params["embed"], tok, got,
                                          rows=True, blk=100)[0]) == rank


def test_weights_follow_all_64_seed_bits():
    cell = tiny_cell()
    layout = cell.family.layout(cell.conf["model"])
    a = weights.make_params(layout, 5)["embed"]
    b = weights.make_params(layout, 5 + 2**33)["embed"]
    assert float(abs(a - b).max()) > 0
