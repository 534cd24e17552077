"""Compile-only checks of the served reuse kernels for a described TPU v5e.

Nothing here runs on a chip: each kernel is lowered and compiled by the TPU
compiler for a `v5e:2x2` topology described in-process, at the real site
shapes of qwen3-32b (the configuration `chip_smoke.py` serves), and so is a
tiny decode step, to see that its GEMM kernels read the stacked weights in
place. Interpret
mode accepts block shapes and memory spaces the chip's compiler refuses, so
these tests are the CPU-side guard that the served step's kernels still
compile for the chip. Each result must carry the kernel as a
`tpu_custom_call` under its own name.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import backend
from repro.kernels.delta_quant import delta_quant
from repro.kernels.reuse_matmul import reuse_matmul
from repro.kernels.reuse_matmul_ragged import reuse_matmul_ragged
from repro.models import init_params
from repro.roofline.hlo_parse import pallas_kernel_calls
from repro.serve.serve_step import (
    build_reuse_engine,
    init_serve_state,
    jit_decode,
)

# attn_qkv is output-stationary (K=5120 -> N=10240); mlp_out is the one
# input-stationary site (K=25600 > 4·N=20480).
SITES = ("attn_qkv", "mlp_out")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def specs():
    return build_reuse_engine(get_config("qwen3-32b"), impl="pallas").sites


def _compile(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("site,m", [("attn_qkv", 8), ("mlp_out", 8),
                                    ("attn_qkv", 16)])
def test_delta_quant_compiles(one_chip, specs, site, m):
    spec = specs[site]
    k = spec.in_features

    def fn(x, prev_q, scale):
        return delta_quant(x, prev_q, scale, block_m=spec.block_m,
                           block_k=spec.block_k)

    hlo = _compile(fn, one_chip, ((m, k), jnp.bfloat16), ((m, k), jnp.int8),
                   ((), jnp.float32))
    assert pallas_kernel_calls(hlo) == {"delta_quant": 1}


@pytest.mark.parametrize("dataflow", ["output", "input"])
@pytest.mark.parametrize("site", SITES)
def test_reuse_matmul_compiles(one_chip, specs, site, dataflow):
    spec = specs[site]
    m, k, n = 8, spec.in_features, spec.out_features
    gm, gk = m // spec.block_m, k // spec.block_k

    def fn(delta, w, prev_out, mask):
        return reuse_matmul(delta, w, prev_out, mask, block_m=spec.block_m,
                            block_n=spec.block_n, block_k=spec.block_k,
                            dataflow=dataflow)

    hlo = _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                   ((k, n), jnp.bfloat16), ((m, n), jnp.float32),
                   ((gm, gk), jnp.int32))
    assert pallas_kernel_calls(hlo) == {f"reuse_matmul_{dataflow}": 1}


@pytest.mark.parametrize("site", SITES)
def test_reuse_matmul_ragged_compiles(one_chip, specs, site):
    spec = specs[site]
    m, k, n = 8, spec.in_features, spec.out_features
    gm, kb = m // spec.block_m, k // spec.block_k // 2  # half-extent budget

    def fn(delta, w, prev_out, counts, idx):
        return reuse_matmul_ragged(delta, w, prev_out, counts, idx,
                                   block_m=spec.block_m, block_n=spec.block_n,
                                   block_k=spec.block_k)

    hlo = _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                   ((k, n), jnp.bfloat16), ((m, n), jnp.float32),
                   ((gm,), jnp.int32), ((gm, kb), jnp.int32))
    assert pallas_kernel_calls(hlo) == {"reuse_matmul_ragged": 1}


def test_init_params_fuses_f32_draws(one_chip):
    """Each weight is drawn in f32 and cast to bf16 inside one compiled
    program, so the chip never holds an f32 copy of a weight: temporaries
    stay a small fraction of the bf16 parameters."""
    cfg = get_config("qwen3-32b").with_layers(1)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = jax.jit(init_params, static_argnums=0, out_shardings=one_chip).lower(
        cfg, key).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 0.1 * mem.output_size_in_bytes


# Decode steps small enough to compile in seconds. qwen3's mlp_out (K=1280 >
# 4·N=1024) is input-stationary, as at its published widths. The last case's
# d_ff (1100) is off the tile grid, so its MLP stacks would need padding.
TINY = {
    "qwen3-32b": ("qwen3-32b", 1280, {
        "delta_quant": 4, "reuse_matmul_output": 3, "reuse_matmul_input": 1}),
    "nemotron-4-15b": ("nemotron-4-15b", 1024, {
        "delta_quant": 4, "reuse_matmul_output": 4}),
    "qwen3-32b-unaligned": ("qwen3-32b", 1100, {
        "delta_quant": 4, "reuse_matmul_output": 3, "reuse_matmul_input": 1}),
}
_GEMM_CALL = re.compile(r'reuse_matmul_\w+/pallas_call"')
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, frontend")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@pytest.mark.parametrize("case", TINY)
def test_decode_step_reads_site_weights_in_place(one_chip, monkeypatch, case):
    """The compiled decode step's GEMM kernels take the stacked parameters
    `[L, K, N]` themselves where K and N are tile multiples; a layer's
    `[K, N]` weight is made only in the basic arm of a site's mode branch.
    A site off the tile grid pads its layer's slice, never the stack."""
    arch, d_ff, kernels = TINY[case]
    cfg = dataclasses.replace(
        get_config(arch).reduced(), n_layers=3, d_model=256, n_heads=2,
        n_kv_heads=1, head_dim=128, d_ff=d_ff, vocab=384,
        param_dtype="bfloat16")
    monkeypatch.setattr(backend, "best", lambda: backend.PALLAS)
    engine = build_reuse_engine(cfg, impl="pallas")

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), jax.eval_shape(tree))

    args = (shapes(lambda: init_params(cfg, jax.random.PRNGKey(0))),
            jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip),
            shapes(lambda: init_serve_state(cfg, 8, 64)),
            shapes(lambda: engine.init_cache(8)))
    hlo = jit_decode(cfg, engine).lower(*args).compile().as_text()
    assert pallas_kernel_calls(hlo) == kernels

    aligned, padded, expected = {}, [], []
    for name, s in engine.sites.items():
        k, n = s.in_features, s.out_features
        kp, np_ = _ceil_to(k, s.block_k), _ceil_to(n, s.block_n)
        if (k, n) == (kp, np_):
            aligned[f"{k},{n}"] = name
            expected.append(f"3,{k},{n}")
        else:
            padded.append(f"3,{kp},{np_}")
            expected.append(f"1,{kp},{np_}")
    assert len(padded) == (2 if case.endswith("unaligned") else 0)
    # operands: mask, sel, layer, delta, weight, prev_out
    weights = [re.findall(r"bf16\[([\d,]+)\]",
                          _OPERANDS.search(line).group(1))[1]
               for line in hlo.splitlines()
               if "tpu_custom_call" in line and _GEMM_CALL.search(line)]
    assert sorted(weights) == sorted(expected)
    for kn in padded:
        assert f"bf16[{kn}]" not in hlo
    made = [(m.group(1), line) for line in hlo.splitlines()
            for m in [re.search(r"= bf16\[(\d+,\d+)\]", line)]
            if m and m.group(1) in aligned]
    assert made
    for kn, line in made:
        assert (f"/layer/reuse_site:{aligned[kn]}/cond/branch_0_fun/"
                in _OP_NAME.search(line).group(1)), line


def test_pattern_decode_step_reads_each_kinds_stack_in_place(one_chip,
                                                            monkeypatch):
    """A pattern stack's GEMM kernels take each kind's stacked weights
    `[L_kind, K, N]` themselves, and its Mamba2 mixers write their SSM
    state in place in the kind's state stack, under `ssm_update`."""
    cfg = dataclasses.replace(
        get_config("nemotron-h-47b").reduced(), d_model=256, n_heads=2,
        n_kv_heads=1, head_dim=128, d_ff=1024, vocab=384, ssm_head_dim=64,
        ssm_state=30, param_dtype="bfloat16")
    monkeypatch.setattr(backend, "best", lambda: backend.PALLAS)
    engine = build_reuse_engine(cfg, impl="pallas")

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), jax.eval_shape(tree))

    args = (shapes(lambda: init_params(cfg, jax.random.PRNGKey(0))),
            jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip),
            shapes(lambda: init_serve_state(cfg, 8, 64)),
            shapes(lambda: engine.init_cache(8)))
    hlo = jit_decode(cfg, engine).lower(*args).compile().as_text()
    calls = cfg.n_layers * 2
    assert pallas_kernel_calls(hlo) == {"delta_quant": calls,
                                        "reuse_matmul_output": calls}
    weights = [re.findall(r"bf16\[([\d,]+)\]",
                          _OPERANDS.search(line).group(1))[1]
               for line in hlo.splitlines()
               if "tpu_custom_call" in line and _GEMM_CALL.search(line)]
    want = []
    for name, s in engine.sites.items():
        assert s.in_features % s.block_k == 0 == s.out_features % s.block_n
        n = engine.stacking[name]
        want += [f"{n},{s.in_features},{s.out_features}"] * n
    assert sorted(weights) == sorted(want)
    h = "f32[2,8,{},64,30]".format(cfg.n_ssm_heads)
    writes = [_OP_NAME.search(line).group(1) for line in hlo.splitlines()
              if line.lstrip().startswith(("%", "ROOT"))
              and f" = {h}" in line and "dynamic-update-slice(" in line]
    assert writes
    for name in writes:
        assert "/layer/mamba/ssm_update/" in name, name
