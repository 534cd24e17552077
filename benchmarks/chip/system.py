"""The system under test: the program's served prefill and decode steps.

Built from a configuration file's `program` group: the program's own config
entry cut to the stated depth, with the stated field overrides, and the
reuse engine of `serve --reuse` (Pallas substrate, default policy, no
controller). Every published key the program has a field for (the
architecture module's `program_fields`) is checked against that field, and
the module's `check_program` against the program's structure, so the program
runs what the file states.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from collections import Counter
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.reuse_cache import resolve_exec_path
from repro.kernels import backend
from repro.models import init_params
from repro.serve.scheduler import reset_slot
from repro.serve.serve_step import (
    build_reuse_engine,
    greedy_sample,
    init_serve_state,
    jit_decode,
    jit_prefill,
)


def program_config(conf: dict, family):
    """The program's config of `conf`, checked against the file through its
    architecture module `family` (`arch.resolve`)."""
    prog = conf["program"]
    cfg = get_config(prog["arch"]).with_layers(prog["layers"])
    cfg = dataclasses.replace(cfg, **prog.get("overrides", {}))
    wrong = {}
    for key, (field, conv) in family.program_fields.items():
        if key in conf["model"]:
            want = conv(conf["model"][key])
            if getattr(cfg, field) != want:
                wrong[key] = (getattr(cfg, field), want)
    wrong.update(family.check_program(cfg))
    if wrong:
        raise ValueError(f"program config {cfg.name} differs from "
                         f"{conf['name']}: {wrong} (program, file)")
    return cfg


# A Pallas kernel lowers to a `tpu_custom_call` whose op_name metadata ends
# in "<kernel name>/pallas_call" (the `name=` given to pl.pallas_call).
_PALLAS_NAME_RE = re.compile(r'op_name="[^"]*?([\w.\-]+)/pallas_call"')


def pallas_kernel_calls(hlo_text: str) -> dict[str, int]:
    calls: Counter = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _PALLAS_NAME_RE.search(line)
            calls[m.group(1) if m else "<unnamed>"] += 1
    return dict(calls)


def require_pallas(engine, hlo: str) -> dict[str, int]:
    """Fail unless the compiled decode step runs every reuse site on
    compiled Pallas: the fused delta kernel and each site's GEMM kernel."""
    sub = backend.for_impl(engine.impl)
    if sub is not backend.PALLAS:
        raise RuntimeError(f"reuse substrate is {sub.name!r}, not compiled "
                           "Pallas")
    want = {"delta_quant"}
    for name, spec in engine.sites.items():
        path = resolve_exec_path(spec, engine.impl)
        if path == "kernel":
            want.add(f"reuse_matmul_{spec.dataflow}")
        elif path == "ragged":
            want.add("reuse_matmul_ragged")
        else:
            raise RuntimeError(f"site {name!r} resolves to exec_path "
                               f"{path!r}, which runs no Pallas kernel")
    calls = pallas_kernel_calls(hlo)
    missing = sorted(want - set(calls))
    if missing:
        raise RuntimeError(f"compiled decode step lacks Pallas kernels "
                           f"{missing}; found {calls}")
    return calls


def _aval(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


@dataclasses.dataclass
class System:
    cfg: Any
    engine: Any                 # ReuseEngine, or None with reuse off
    params: Any
    batch: int
    cache_len: int
    prefill: Any                # (params, tokens [B,P], state) -> (logits, state)
    decode: Any                 # (params, tok [B,1], state, rcache) -> ...
    sample: Any                 # logits -> tokens, on the device
    new_state: Any              # () -> a fresh serving state
    reset_lanes: Any            # rcache -> rcache with every lane reset
    kernel_calls: dict
    rcache: Any = None

    def counters(self) -> dict[str, tuple[int, int]]:
        """Cumulative (skipped, computed) input tiles per reuse site."""
        if self.rcache is None:
            return {}
        got = jax.device_get(_tile_sums(self.rcache))
        return {k: (int(s), int(c)) for k, (s, c) in got.items()}

    def scopes(self) -> dict:
        """{module: {instruction: op_name}} of the compiled steps."""
        from chip.trace_reduce import hlo_scopes

        out = {}
        for step in (self.prefill, self.decode):
            out.update(hlo_scopes(step.as_text()))
        return out

    def free(self) -> None:
        """Drop every device buffer the serving loop holds but the weights."""
        self.rcache = None
        self.prefill = self.decode = None


@jax.jit
def _tile_sums(rcache):
    return {k: (jnp.sum(e["sensor"]["skipped_tiles"]),
                jnp.sum(e["sensor"]["computed_tiles"]))
            for k, e in rcache.items()}


def build(conf: dict, family, traffic: dict, params, *,
          check_kernels: bool) -> System:
    cfg = program_config(conf, family)
    want = jax.eval_shape(init_params, cfg, jax.random.PRNGKey(0))
    if _aval(params) != _aval(want):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
    batch, cache_len = traffic["requests_per_wave"], traffic["cache_len"]
    engine = (build_reuse_engine(cfg, impl="pallas")
              if conf["program"]["reuse"] else None)

    new_state = jax.jit(functools.partial(init_serve_state, cfg, batch,
                                          cache_len))
    state = jax.eval_shape(new_state)
    rcache = engine.init_cache(batch) if engine is not None else None
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    prompt = jax.ShapeDtypeStruct((batch, traffic["prompt_len"]), jnp.int32)
    decode = jit_decode(cfg, engine).lower(
        _aval(params), tok, state, _aval(rcache)).compile()
    prefill = jit_prefill(cfg).lower(_aval(params), prompt, state).compile()
    calls = {}
    if engine is not None and check_kernels:
        calls = require_pallas(engine, decode.as_text())

    def reset_all(rc):
        for slot in range(batch):
            rc = reset_slot(rc, slot)
        return rc

    return System(
        cfg=cfg, engine=engine, params=params, batch=batch,
        cache_len=cache_len, prefill=prefill, decode=decode,
        sample=jax.jit(greedy_sample), new_state=new_state,
        reset_lanes=jax.jit(reset_all, donate_argnums=0),
        kernel_calls=calls, rcache=rcache)
