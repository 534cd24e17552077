"""Trace reduction on a trace recorded on a TPU v5e: a 0.5 s window of the
`qwen3-32b-l8.chat` cell (one wave set-up and prefill, then decode steps),
with the op scopes of the compiled steps it ran."""

import json
import pathlib

import pytest

from chip import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
SITES = {"attn_qkv", "attn_out", "mlp_in", "mlp_out"}


@pytest.fixture(scope="module")
def trace():
    scopes = json.loads((DATA / "qwen3-32b-l8.chat.scopes.json").read_text())
    return trace_reduce.load(str(DATA / "qwen3-32b-l8.chat.xplane.pb"),
                             scopes)


def test_one_chip_and_the_harness_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    w = trace.window()
    assert w is not None
    assert len(trace.spans_named("bench:prefill", within=w)) == 1
    steps = trace.spans_named("bench:decode", within=w)
    assert len(steps) >= 2
    # each decode step holds its host pull
    pulls = trace.spans_named("bench:pull", within=w)
    assert all(any(s.start <= p.start and p.end <= s.end for p in pulls)
               for s in steps)


def test_busy_time_and_scopes(trace):
    w = trace.window()
    steps = trace.spans_named("bench:decode", within=w)
    assert 0 < trace.busy_ns(w.start, w.end) <= w.end - w.start
    for s in steps:     # the device works through most of each step
        assert trace.busy_ns(s.start, s.end) > 0.5 * (s.end - s.start)
    site = trace.op_ns(steps, lambda n, s: "reuse_site:" in s)
    gemm = trace.op_ns(steps, lambda n, s: "/reuse_matmul_" in s
                       and s.endswith("/pallas_call"))
    delta = trace.op_ns(steps, lambda n, s: "jit(delta_quant)" in s)
    leaves = trace.op_ns(steps, lambda n, s: True)
    assert 0 < delta < gemm < site < leaves
    seen = {p.split(":", 1)[1] for d in trace.devices.values()
            for _, scope in d.keys for p in scope.split("/")
            if p.startswith("reuse_site:")}
    assert seen == SITES


def test_breakdown(trace):
    w = trace.window()
    ops = trace.top_ops(w.start, w.end)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert any(name.startswith("reuse_matmul_") for name, _ in ops)
    gaps = trace.idle_gaps(w.start, w.end)
    idle = (w.end - w.start) - trace.busy_ns(w.start, w.end)
    assert sum(s for _, s in gaps) == pytest.approx(idle * 1e-9, rel=1e-6)
