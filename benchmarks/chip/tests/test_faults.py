"""The whole run, the look for a chip skipped, with the timed path broken
underneath (`faults.py`): each fault a one-chip serving cell can have makes
`correct` false. A fault that breaks the serving state or leaves lanes
uncomputed moves every compared number past its limit, the mean included;
a single altered token moves the widest ones."""

import numpy as np
import pytest

from chip import check, faults
from chip.tests.conftest import run_tiny


def _widest(name):
    return check.NUMBERS[name][2] == "widest"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_run_incorrect(fault):
    r = run_tiny(21, fault=faults.FAULTS[fault])
    assert r["correct"] is False, r["check"]
    numbers = [n for n in r["check"] if n in check.NUMBERS]
    if fault == "token_altered":
        numbers = [n for n in numbers if _widest(n)]
    assert numbers
    for n in numbers:
        assert r["check"][n]["value"] > r["check"][n]["limit"], (n, r["check"])


def test_same_seed_without_fault_is_correct():
    r = run_tiny(21)
    assert r["correct"] is True, r["check"]
    for n, v in r["check"].items():
        assert np.isfinite(v["value"]) and v["value"] <= v["limit"], n
