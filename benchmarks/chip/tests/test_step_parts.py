"""The readers of the served step's named parts: `layer_slice_device_ms`
(ops under the segment `layer_scan` and not `layer`) and `head_device_ms`
(ops under the segment `head`), on hand-made traces and on the scopes of the
real qwen3-32b-l8 decode step compiled on a TPU v5e (`System.scopes()`)."""

import json
import pathlib
import types

import pytest

from chip import harness
from chip.trace_reduce import Op, Span, Trace

HERE = pathlib.Path(__file__).resolve().parents[1]
DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
PARTS = ("embed", "layer_scan", "head")
SLICE = "jit(step)/layer_scan/while/body/squeeze"
LAYER = ("jit(step)/layer_scan/while/body/closed_call/layer/"
         "reuse_site:mlp_in/cond/branch_1_fun/jit(reuse_matmul)/"
         "reuse_matmul_output/pallas_call")
HEAD = "jit(step)/head/bsd,dv->bsv/dot_general"


def read(metric: str, trace: Trace):
    reader = harness.load_module(HERE / "metrics" / f"{metric}.py")
    return reader.read(types.SimpleNamespace(trace=trace))


def trace(slice_scope=SLICE, layer_scope=LAYER, head_scope=HEAD,
          decode=True, devices=True):
    ops = [Op("dynamic-slice_bitcast_fusion.24", slice_scope, 10, 30),
           Op("reuse_matmul_output.15", layer_scope, 30, 60),
           Op("convolution_bitcast_fusion", head_scope, 62, 70),
           Op("dynamic-slice_bitcast_fusion.24", slice_scope, 110, 124),
           Op("reuse_matmul_output.15", layer_scope, 124, 128),
           Op("convolution_bitcast_fusion", head_scope, 130, 136),
           # prefill's own slices and head, outside every decode step
           Op("dynamic-slice_bitcast_fusion.3",
              slice_scope.replace("jit(step)", "jit(<lambda>)"), 200, 250),
           Op("convolution_bitcast_fusion.1",
              head_scope.replace("jit(step)", "jit(<lambda>)"), 250, 260)]
    spans = [Span("bench:window", 0, 300, 0),
             Span("bench:prefill", 190, 270, 1)]
    if decode:
        spans += [Span("bench:decode", 5, 80, 1),
                  Span("bench:decode", 105, 140, 1)]
    return Trace.of({DEV: ops} if devices else {}, spans)


def test_layer_slices_per_decode_step():
    # the two steps' slices, 20 and 14 ns; `layer` is a segment, so the
    # slices under `layer_scan` count and the kernel under `layer` does not
    assert read("layer_slice_device_ms", trace()) == pytest.approx(
        (20 + 14) / 2 / 1e6)


def test_head_per_decode_step():
    assert read("head_device_ms", trace()) == pytest.approx(
        (8 + 6) / 2 / 1e6)


@pytest.mark.parametrize("metric", ["layer_slice_device_ms",
                                    "head_device_ms"])
def test_nothing_to_read(metric):
    assert read(metric, trace(decode=False)) is None
    assert read(metric, trace(devices=False)) is None
    # a program whose steps name none of their parts: scopes as before the
    # parts were named
    unnamed = trace(slice_scope="jit(step)/while/body/squeeze",
                    layer_scope="jit(step)/while/body/closed_call/"
                    "reuse_site:mlp_in/pallas_call",
                    head_scope="jit(step)/bsd,dv->bsv/dot_general")
    assert read(metric, unnamed) is None


@pytest.fixture(scope="module")
def scopes():
    return json.loads((DATA / "qwen3-32b-l8.chat.step-parts.scopes.json")
                      .read_text())


def test_real_step_lies_in_one_part_each(scopes):
    traced = [s.split("/")[1:] for s in scopes["jit_step"].values()
              if s.startswith("jit(")]
    assert len(traced) > 1000
    for segs in traced:
        assert segs[0] in PARTS and sum(s in PARTS for s in segs) == 1, segs


def one_op(name, scope):
    """A decode step holding one op, beside an op of a layer, so that the
    trace names the layer scan."""
    return Trace.of({DEV: [Op(name, scope, 10, 20),
                           Op("reuse_matmul_output.15", LAYER, 20, 25)]},
                    [Span("bench:window", 0, 30, 0),
                     Span("bench:decode", 5, 28, 1)])


def test_real_slices_and_head_fall_under_their_readers(scopes):
    step = scopes["jit_step"]
    slices = [n for n in step if n.startswith("dynamic-slice_bitcast_fusion")]
    heads = [n for n, s in step.items() if "bsd,dv->bsv" in s]
    # the weight copies and the head GEMM that lead a traced decode step's
    # breakdown on the chip
    assert {f"dynamic-slice_bitcast_fusion.{i}" for i in (20, 22, 24, 26)} \
        <= set(slices)
    assert "convolution_bitcast_fusion" in heads
    for name in slices:
        t = one_op(name, step[name])
        assert read("layer_slice_device_ms", t) == pytest.approx(10 / 1e6)
        assert read("head_device_ms", t) is None
    for name in heads:
        t = one_op(name, step[name])
        assert read("head_device_ms", t) == pytest.approx(10 / 1e6)
        assert read("layer_slice_device_ms", t) == 0.0
