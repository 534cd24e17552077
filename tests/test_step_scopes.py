"""The served steps' named parts.

Every instruction the compiled prefill and decode steps trace from the model
lies in exactly one of the parts `embed`, `layer_scan` and `head`, named by
`jax.named_scope`. Inside `layer_scan`, an instruction is either in `layer`
(what one layer computes) or is the scan's own slicing and write-back of its
operands, which a device trace can then attribute by scope alone. The names
are metadata only: with every named scope made a null context, the compiled
steps hold the same instructions; the persistent compile cache keys them, so
an executable loaded from it carries its own source's scopes.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve.serve_step import (
    build_reuse_engine,
    init_serve_state,
    jit_decode,
    jit_prefill,
)

PARTS = ("embed", "layer_scan", "head")
BATCH, CACHE_LEN, PROMPT = 8, 48, 16
# qwen3: SwiGLU, qk-norm, tied head when reduced; nemotron: squared-ReLU
# without gate, untied head; nemotron-h: a pattern stack of Mamba2, MLP and
# attention layers, unrolled in pattern order
ARCHS = ("qwen3-32b", "nemotron-4-15b", "nemotron-h-47b")
STEPS = ("decode", "prefill")
# what the scan itself lowers to, below `layer_scan`: the loop, its counter
# and condition, the per-layer slices and write-backs of its operands, the
# stacked outputs' buffers, constants hoisted out of the layer's call, and
# the serving state's length update
SCAN_OWN = re.compile(
    r"^(while(/cond/\w+|/body/(squeeze|dynamic_slice|dynamic_update_slice"
    r"|add|closed_call))?|broadcast_in_dim|add)$")
# a pattern stack runs its layers unrolled, outside any loop: besides the
# above, its static per-layer slices and in-place write-backs of its kinds'
# stacks
PATTERN_OWN = re.compile(
    r"^(while(/cond/\w+|/body/(squeeze|dynamic_slice|dynamic_update_slice"
    r"|add|closed_call))?|broadcast_in_dim|add|slice|dynamic_update_slice)$")
# the ops through which the layer's weights reach it: the loop's slices, or
# a pattern stack's static ones
WEIGHT_SLICES = ("squeeze", "dynamic_slice")
PATTERN_WEIGHT_SLICES = ("squeeze", "dynamic_slice", "slice")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled(arch: str, step: str) -> str:
    cfg = get_config(arch).reduced()
    params = jax.eval_shape(init_params, cfg, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: init_serve_state(cfg, BATCH, CACHE_LEN))
    if step == "prefill":
        toks = jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)
        return jit_prefill(cfg).lower(params, toks, state).compile().as_text()
    engine = build_reuse_engine(cfg, impl="pallas")
    rcache = jax.eval_shape(lambda: engine.init_cache(BATCH))
    toks = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    return jit_decode(cfg, engine).lower(
        params, toks, state, rcache).compile().as_text()


@pytest.fixture(scope="module")
def hlo():
    cache = {}

    def get(arch, step):
        if (arch, step) not in cache:
            cache[arch, step] = _compiled(arch, step)
        return cache[arch, step]

    return get


def _traced(text: str) -> list[list[str]]:
    """The scope segments below the step's own `jit(...)` of every
    instruction the step traced; instructions XLA adds itself carry no
    `jit(` op_name, and one that XLA made of several carries their names
    joined by `;`."""
    names = (n for m in _OP_NAME.finditer(text) for n in m.group(1).split(";"))
    return [n.split("/")[1:] for n in names if n.startswith("jit(")]


def _instructions(text: str) -> list[str]:
    """The program's computations without metadata: each instruction's
    `metadata={...}` taken out, and the source-location tables (file names,
    functions, stack frames) between the module's header and its first
    computation left out."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if line.startswith(("HloModule", "%", "ENTRY", " ", "}"))]


cases = pytest.mark.parametrize("arch,step", [(a, s) for a in ARCHS
                                              for s in STEPS])


@cases
def test_every_traced_op_lies_in_one_part(hlo, arch, step):
    ops = _traced(hlo(arch, step))
    assert ops
    for segs in ops:
        assert segs[0] in PARTS, segs
        assert sum(s in PARTS for s in segs) == 1, segs
    assert {segs[0] for segs in ops} == set(PARTS)


@cases
def test_scan_ops_are_layers_or_the_scans_own(hlo, arch, step):
    scan = [segs[1:] for segs in _traced(hlo(arch, step))
            if segs[0] == "layer_scan"]
    slices = [s for s in scan if "layer" not in s]
    assert len(slices) < len(scan)
    pattern = bool(get_config(arch).layer_pattern)
    own = PATTERN_OWN if pattern else SCAN_OWN
    for segs in slices:
        assert own.match("/".join(segs)), segs
    # the layer's weights reach it through the scan's slices
    weight_slices = PATTERN_WEIGHT_SLICES if pattern else WEIGHT_SLICES
    assert any(s[-1] in weight_slices for s in slices)


@cases
def test_reuse_sites_stay_inside_layer(hlo, arch, step):
    sites = [segs for segs in _traced(hlo(arch, step))
             if any(s.startswith("reuse_site:") for s in segs)]
    for segs in sites:
        first = next(i for i, s in enumerate(segs)
                     if s.startswith("reuse_site:"))
        assert segs[0] == "layer_scan" and "layer" in segs[:first], segs
    # decode serves every linear site through the reuse engine; prefill
    # runs none
    assert bool(sites) == (step == "decode")


@cases
def test_scopes_change_only_metadata(hlo, monkeypatch, arch, step):
    scoped = hlo(arch, step)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled(arch, step)
    assert "/layer_scan/" in scoped and "/layer_scan/" not in plain
    ops = _instructions(scoped)
    assert len([op for op in ops if " = " in op]) > 100
    assert ops == _instructions(plain)


def test_cached_step_keeps_its_own_scopes(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    def step_named(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0
        return step

    names = ("jax_compilation_cache_dir",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = jnp.ones((64, 64))
        first = jax.jit(step_named("before")).lower(x).compile().as_text()
        again = jax.jit(step_named("after")).lower(x).compile().as_text()
        assert any(tmp_path.iterdir())
        assert "/before/" in first
        assert "/after/" in again and "/before/" not in again
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()
