"""Trace reduction on hand-made intervals."""

from chip.trace_reduce import Op, Span, Trace

DEV = "/device:TPU:0"


def trace():
    # a conditional encloses the two ops it runs, as on a TPU's op line
    ops = [Op("conditional.1", "jit(step)/reuse_site:attn_qkv/cond", 10, 30),
           Op("jit_delta_quant_.4",
              "jit(step)/reuse_site:attn_qkv/cond/jit(delta_quant)", 10, 14),
           Op("reuse_matmul_output.7", "jit(step)/reuse_site:attn_qkv/cond/"
              "jit(reuse_matmul)/reuse_matmul_output/pallas_call", 15, 30),
           Op("fusion.2", "jit(step)/attention", 40, 50),
           Op("reuse_matmul_output.1", "jit(step)/reuse_site:mlp_out/cond/"
              "jit(reuse_matmul)/reuse_matmul_output/pallas_call", 70, 95)]
    spans = [Span("bench:window", 0, 100, 0),
             Span("bench:decode", 5, 55, 1), Span("bench:pull", 30, 55, 2),
             Span("bench:decode", 60, 98, 1)]
    return Trace.of({DEV: ops}, spans)


def test_busy_is_the_union_of_op_intervals():
    t = trace()
    assert t.busy_intervals(DEV) == [[10, 30], [40, 50], [70, 95]]
    assert t.busy_ns(0, 100) == 20 + 10 + 25
    assert t.busy_ns(15, 45) == 15 + 5
    assert t.busy_ns(12, 20) == 8
    assert t.busy_ns(31, 39) == 0


def test_op_time_by_scope_within_steps():
    t = trace()
    steps = t.spans_named("bench:decode", within=t.window())
    assert len(steps) == 2
    # innermost ops only: the conditional's own span is not counted again
    assert t.op_ns(steps, lambda n, s: "reuse_site:" in s) == 4 + 15 + 25
    assert t.op_ns(steps, lambda n, s: "reuse_matmul" in n) == 15 + 25
    assert t.op_ns(steps[1:], lambda n, s: "attention" in s) == 0


def test_idle_gaps_go_to_the_innermost_open_span():
    t = trace()
    gaps = dict(t.idle_gaps(0, 100))
    # each gap goes whole to the span open at its middle: 30-40 to the
    # pull; 0-10, 50-70 and 95-100 to a decode span
    assert {k: round(v * 1e9) for k, v in gaps.items()} == {
        "bench:pull": 10, "bench:decode": 35}
    assert round(sum(gaps.values()) * 1e9) == 100 - t.busy_ns(0, 100)
    assert t.host_span_at(57) == "no span"
    (name, seconds), *_ = t.top_ops(0, 100)
    assert name == "reuse_matmul_output.1 reuse_site:mlp_out"
    assert round(seconds * 1e9) == 25
