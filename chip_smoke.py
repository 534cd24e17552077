#!/usr/bin/env python3
"""Smoke test of the served reuse path on a TPU.

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # four chips: sharded serve vs one chip

Serves qwen3-32b at its published widths, cut to the first 8 of its 64
layers (one chip's share of the model), with random weights drawn from a
fixed seed, through the entry point a user calls (`repro.launch.serve.main`,
in this process). Phases, each of which raises on failure:

  a. device       platform, device kind and count, JAX and libtpu versions,
                  kernel substrate. Anything but a TPU whose reuse sites run
                  compiled Pallas stops the run here.
  b. kernels      every reuse kernel at each served site's real shape, at
                  tile skip rates 0, 0.5 and 1, against its `kernels/ref.py`
                  oracle.
  c. serve        the serving CLI with reuse on (kernel check on), with
                  reuse off, and with every site pinned to the ragged kernel
                  through a tuned-policy table this script writes.
  d. correctness  teacher-forced decode logits: reuse off against the
                  model's uncached forward in f32, and reuse on against the
                  same engine with every site pinned to basic mode.

`--four-chips` runs only the sharded check: the serving CLI with the reuse
cache sharded 4 ways over the chips, and the sharded step's logits and
counters against the same step on one chip.

Times printed are smoke output, not a benchmark. The last line of stdout is
`{"ok": true, "device": {...}}`, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import backend, ops  # noqa: E402
from repro.kernels.ref import expand_block_mask  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import forward, init_params, output_logits  # noqa: E402
from repro.quant import quantize_int8  # noqa: E402
from repro.serve.serve_step import (  # noqa: E402
    build_reuse_engine,
    init_serve_state,
    jit_decode,
    jit_prefill,
)

ARCH, LAYERS = "qwen3-32b", 8
SLOTS, PROMPT_LEN, CACHE_LEN = 8, 128, 2048
DECODE_STEPS = 8   # teacher-forced decode steps compared in d and --four-chips
SEED = 0
OUT_DIR = ROOT / "smoke_out"
SERVE_ARGV = [
    "--arch", ARCH, "--layers", str(LAYERS),
    "--batch-slots", str(SLOTS), "--prompt-len", str(PROMPT_LEN),
    "--cache-len", str(CACHE_LEN), "--max-new", "32", "--requests", "16",
    "--seed", str(SEED),
]

# f32 unit roundoff. Two f32 sums of the same n terms taken in different
# orders each lie within gamma_n = n*u/(1 - n*u) of sum(|terms|) of the exact
# sum (recursive-summation bound), so they differ by at most twice that.
F32_U = 2.0 ** -24
# Served (bf16) logits against the f32 reference, as relative L2 error per
# position: bf16 keeps 8 significant bits, so each rounding of the residual
# stream, a norm or a matmul output is off by up to 2^-9 relative. About ten
# such roundings per layer over 8 layers, adding as independent errors, give
# sqrt(80) * 2^-9 = 1.7%; the bound allows three times that for the
# amplification of norms and attention softmax.
REF_REL_L2 = 0.05
# Reuse against basic mode, same metric, bounded relative to what the int8
# activation quantizer already does to basic mode (basic against reuse off).
# Basic rounds each dequantized input to bf16 once; reuse rounds each step's
# delta to bf16 and sums the rounded deltas into prev_out, so a site output
# drifts by up to 2^-9 relative per step. A drift that moves a downstream
# activation across a quantization boundary changes its int8 code by one: a
# full quantization step, the size of the quantizer's own error. Two
# independent errors of that size add to sqrt(2) times one; the bound is 2.
REUSE_VS_QUANT = 2.0


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Seconds spent compiling (or loading from the persistent cache), from
    JAX's own compile events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ------------------------------------------------------------------ a. device


def phase_device(want_count: int) -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log("device", f"{info} jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r} devices")
    if info["count"] < want_count:
        raise SystemExit(f"need {want_count} chips, found {info['count']}")
    sub = backend.for_impl("pallas")
    log("device", f"substrate {backend.describe()}")
    if sub is not backend.PALLAS:
        raise SystemExit(f"reuse substrate is {sub.name!r}, not compiled "
                         "Pallas")
    return info


# ----------------------------------------------------------------- b. kernels


def _inputs_at_skip(spec, m: int, skip: float, key):
    """x and a prev_q whose tile mask skips a `skip` share of tiles: prev_q
    equals quantize(x) on skipped tiles and differs by one code everywhere
    on the others."""
    kx, kt = jax.random.split(key)
    gm, gk = m // spec.block_m, spec.in_features // spec.block_k
    x = jax.random.normal(kx, (m, spec.in_features), jnp.bfloat16)
    scale = jnp.float32(spec.fixed_scale)
    cur_q = quantize_int8(x, scale)
    live = jax.random.uniform(kt, (gm, gk)) >= skip
    live = jnp.repeat(jnp.repeat(live, spec.block_m, 0), spec.block_k, 1)
    moved = jnp.where(cur_q < 0, cur_q + 1, cur_q - 1).astype(jnp.int8)
    return x, jnp.where(live, moved, cur_q), scale


def _gemm_error(out, want, delta, mask, w, prev_out, spec):
    """(max |out - want|, that error over its f32 summation bound)."""
    emask = expand_block_mask(
        mask, *delta.shape, spec.block_m, spec.block_k)
    terms = jnp.abs(prev_out) + jax.lax.dot(
        jnp.abs(delta.astype(jnp.float32)) * emask,
        jnp.abs(w.astype(jnp.float32)), precision=jax.lax.Precision.HIGHEST)
    n = delta.shape[1] + 1
    gamma = n * F32_U / (1 - n * F32_U)
    err = jnp.abs(out - want)
    return float(jnp.max(err)), float(jnp.max(err / (2 * gamma * terms
                                                       + 1e-30)))


def phase_kernels(cfg, m: int) -> None:
    """Each reuse kernel at the served sites' shapes against its oracle.

    delta_quant must equal its oracle bitwise: both compute the same f32
    quotient, round and clip, so codes, bf16 deltas and tile bits agree
    exactly. The GEMMs multiply bf16 operands exactly into f32 and differ
    from the HIGHEST-precision oracle only in the order of the f32 sum, so
    their error must stay within the summation bound (ratio <= 1)."""
    specs = build_reuse_engine(cfg, impl="pallas").sites
    key = jax.random.PRNGKey(SEED)
    for name, spec in specs.items():
        k, n = spec.in_features, spec.out_features
        kw, kp, key = jax.random.split(key, 3)
        w = (jax.random.normal(kw, (k, n), jnp.float32)
             / np.sqrt(k)).astype(jnp.bfloat16)
        prev_out = jax.random.normal(kp, (m, n), jnp.float32)
        for skip in (0.0, 0.5, 1.0):
            key, sub = jax.random.split(key)
            x, prev_q, scale = _inputs_at_skip(spec, m, skip, sub)
            got = ops.delta_quant_fused(
                x, prev_q, scale, block_m=spec.block_m, block_k=spec.block_k,
                interpret=False)
            want = ops.delta_quant_ref(x, prev_q, scale, spec.block_m,
                                       spec.block_k)
            for label, a, b in zip(("codes", "delta", "mask"), got, want):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise AssertionError(
                        f"{name} skip={skip}: delta_quant {label} differ "
                        "from kernels/ref.py")
            _, delta, mask = want
            realized = 1.0 - float(jnp.mean(mask.astype(jnp.float32)))
            ref = ops.reuse_matmul_ref(delta, w, prev_out, mask,
                                       spec.block_m, spec.block_k)
            tile = dict(block_m=spec.block_m, block_n=spec.block_n,
                        block_k=spec.block_k, interpret=False)
            outs = {
                f"reuse_matmul_{df}": ops.reuse_matmul(
                    delta, w, prev_out, mask, dataflow=df, **tile)
                for df in ("output", "input")
            }
            outs["reuse_matmul_ragged"] = ops.reuse_matmul_ragged(
                delta, w, prev_out, mask, **tile)
            errs = []
            for kernel, out in outs.items():
                err, ratio = _gemm_error(out, ref, delta, mask, w, prev_out,
                                         spec)
                errs.append(f"{kernel} {err:.3g} ({ratio:.3g} of bound)")
                if not ratio <= 1.0:
                    raise AssertionError(
                        f"{name} skip={skip}: {kernel} max abs error {err} "
                        f"is {ratio} times its f32 summation bound")
            log("kernels", f"{name} [{m}x{k}]x[{k}x{n}] skip={realized:.2f}: "
                f"delta_quant exact; max abs err " + ", ".join(errs))


# ------------------------------------------------------------------- c. serve


def write_ragged_table(cfg) -> pathlib.Path:
    from repro.core.policy import SiteTunables
    from repro.tune.table import save_table

    OUT_DIR.mkdir(exist_ok=True)
    path = OUT_DIR / "ragged_policy.json"
    sites = build_reuse_engine(cfg, impl="pallas").sites
    save_table(str(path), {s: SiteTunables(exec_path="ragged") for s in sites},
               meta={"written_by": "chip_smoke.py"})
    return path


def run_serve(label: str, argv: list[str], clock: CompileClock) -> dict:
    log("serve", f"{label}: serve {' '.join(argv)}")
    c0, t0 = clock.seconds, time.perf_counter()
    result = serve.main(argv)
    wall = time.perf_counter() - t0
    log("serve", f"{label}: smoke output, not a benchmark: wall {wall:.1f}s, "
        f"compile {clock.seconds - c0:.1f}s, serve loop "
        f"{result['seconds']:.1f}s, peak_bytes_in_use {peak_bytes()}")
    gc.collect()
    return result


def phase_serve(cfg, clock: CompileClock) -> None:
    run_serve("reuse", SERVE_ARGV + ["--reuse", "--check-kernels"], clock)
    run_serve("reuse off", SERVE_ARGV, clock)
    table = write_ragged_table(cfg)
    res = run_serve("ragged", SERVE_ARGV + [
        "--reuse", "--check-kernels", "--tuned-policy", str(table)], clock)
    if "reuse_matmul_ragged" not in res["kernel_calls"]:
        raise AssertionError(f"ragged pass ran {res['kernel_calls']}")


# ------------------------------------------------------------ d. correctness


def decode_tokens(cfg, rng):
    """Prompts [B, P] and teacher-forced decode tokens [B, T]. Each slot
    repeats one token, as a sticky stream does, so its layer-0 inputs repeat
    and reuse skips their tiles."""
    prompts = rng.integers(0, cfg.vocab, (SLOTS, PROMPT_LEN), dtype=np.int32)
    toks = rng.integers(0, cfg.vocab, (SLOTS, 1), dtype=np.int32)
    return prompts, np.repeat(toks, DECODE_STEPS, axis=1)


def served_logits(params, cfg, engine, prompts, toks):
    """Teacher-forced serving through the served prefill and decode steps.
    Returns logits [T+1, B, V] in f32 (the prefill's last position, then
    each decode step) and the final reuse cache."""
    from jax.sharding import NamedSharding, PartitionSpec

    state = init_serve_state(cfg, SLOTS, CACHE_LEN)
    rcache = None if engine is None else engine.init_cache(SLOTS)
    put = jnp.asarray
    if engine is not None and engine.mesh is not None:
        from repro.dist.shard import cache_shardings

        replicated = NamedSharding(engine.mesh, PartitionSpec())
        state = jax.device_put(state, replicated)
        rcache = jax.device_put(
            rcache, cache_shardings(engine, engine.mesh, rcache))

        def put(a):
            return jax.device_put(a, replicated)

    logits, state = jit_prefill(cfg)(params, put(prompts), state)
    out = [np.asarray(logits[:, -1], np.float32)]
    step = jit_decode(cfg, engine)
    for t in range(toks.shape[1]):
        logits, state, rcache = step(params, put(toks[:, t:t + 1]), state,
                                     rcache)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out), rcache


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """The model's uncached forward over `tokens` [1, S] in f32 at HIGHEST
    matmul precision. The bf16 weights are widened inside the matmuls; only
    the embedding table is copied to f32."""

    @jax.jit
    def ref(p, toks):
        p = dict(p, embed=p["embed"].astype(jnp.float32))
        h, *_ = forward(p, cfg, {"tokens": toks})
        return output_logits(p, cfg, h)

    with jax.default_matmul_precision("highest"):
        return np.asarray(ref(params, jnp.asarray(tokens)), np.float32)


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    """Largest relative L2 error over positions (the last axis is V)."""
    num = np.linalg.norm(a - b, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(b, axis=-1), 1e-30)))


def pin_basic(engine):
    engine.sites = {n: dataclasses.replace(s, mode="basic")
                    for n, s in engine.sites.items()}
    return engine


def phase_correctness(cfg, clock: CompileClock) -> None:
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    prompts, toks = decode_tokens(cfg, np.random.default_rng(SEED + 1))

    off, _ = served_logits(params, cfg, None, prompts, toks)
    seq = np.concatenate([prompts[:1], toks[:1]], axis=1)
    ref = reference_logits(params, cfg, seq)[0, PROMPT_LEN - 1:]
    err = rel_l2(off[:, 0], ref)
    log("correctness", f"reuse off vs f32 uncached forward, slot 0, "
        f"{DECODE_STEPS} steps: max rel L2 {err:.4g} (bound {REF_REL_L2}), "
        f"max abs {np.max(np.abs(off[:, 0] - ref)):.4g}, "
        f"max |ref| {np.max(np.abs(ref)):.4g}")
    if not err <= REF_REL_L2:
        raise AssertionError(f"served logits off the f32 reference: {err}")

    engine = build_reuse_engine(cfg, impl="pallas")
    on, rcache = served_logits(params, cfg, engine, prompts, toks)
    skip = engine.sensor_report(rcache).model["tile_skip_rate"]
    basic, _ = served_logits(params, cfg, pin_basic(
        build_reuse_engine(cfg, impl="pallas")), prompts, toks)
    quant = rel_l2(basic[1:], off[1:])
    err = rel_l2(on[1:], basic[1:])
    log("correctness", f"reuse vs basic, {SLOTS} slots, {DECODE_STEPS} "
        f"steps, tile skip {skip:.3f}: max rel L2 {err:.4g} (bound "
        f"{REUSE_VS_QUANT} x {quant:.4g}, the int8 quantizer's own effect: "
        f"basic vs reuse off), max abs {np.max(np.abs(on - basic)):.4g}")
    if not err <= REUSE_VS_QUANT * quant:
        raise AssertionError(f"reuse changed the logits: {err}")
    log("correctness", f"peak_bytes_in_use {peak_bytes()}, compile so far "
        f"{clock.seconds:.1f}s")


# -------------------------------------------------------------- four chips


COUNTER_FIELDS = ("skipped_tiles", "computed_tiles", "skipped_macs",
                  "computed_macs", "skipped_weight_bytes",
                  "total_weight_bytes", "reused_out_elems",
                  "dma_issued_tiles", "grid_steps")


def layer_counters(engine, rcache) -> dict:
    return {(r.site, r.layer): tuple(getattr(r, f) for f in COUNTER_FIELDS)
            for r in engine.sensor_report(rcache).per_layer}


def phase_four_chips(cfg, clock: CompileClock) -> None:
    """The reuse cache sharded over 4 chips against the same step on one.

    Logits and shard-summed counters must be bitwise equal: each shard runs
    the same kernels over its own weight columns with the full K row, so
    every output column is summed in the same order as on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import parse_mesh_spec

    prompts, toks = decode_tokens(cfg, np.random.default_rng(SEED + 1))
    key = jax.random.PRNGKey(SEED)
    params = init_params(cfg, key)
    engine = build_reuse_engine(cfg, impl="pallas")
    one, rcache = served_logits(params, cfg, engine, prompts, toks)
    one_counts = layer_counters(engine, rcache)
    del params, rcache
    gc.collect()
    log("four-chips", f"one chip: {DECODE_STEPS} decode steps, "
        f"peak_bytes_in_use {peak_bytes()}")

    run_serve("sharded", SERVE_ARGV + [
        "--reuse", "--check-kernels", "--mesh", "host:4"], clock)

    mesh = parse_mesh_spec("host:4")
    params = jax.jit(init_params, static_argnums=0, out_shardings=NamedSharding(
        mesh, PartitionSpec()))(cfg, key)
    engine = build_reuse_engine(cfg, impl="pallas")
    engine.shard_sites(4, mesh=mesh)
    four, rcache = served_logits(params, cfg, engine, prompts, toks)
    four_counts = layer_counters(engine, rcache)
    diff = float(np.max(np.abs(four - one)))
    log("four-chips", f"sharded vs one chip: logits max abs diff {diff:.4g} "
        f"({'bitwise equal' if diff == 0 else 'NOT bitwise'}), counters "
        f"{'equal' if four_counts == one_counts else 'DIFFER'} over "
        f"{len(one_counts)} (site, layer) lanes")
    if diff != 0 or four_counts != one_counts:
        bad = [k for k in one_counts if four_counts.get(k) != one_counts[k]]
        raise AssertionError(f"sharding changed the result: logits diff "
                             f"{diff}, counter lanes differ at {bad[:8]}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serve on four chips against "
                    "one chip")
    args = ap.parse_args(argv)

    info = phase_device(4 if args.four_chips else 1)
    log("device", f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH).with_layers(LAYERS)
    if args.four_chips:
        phase_four_chips(cfg, clock)
    else:
        phase_kernels(cfg, SLOTS)
        phase_serve(cfg, clock)
        phase_correctness(cfg, clock)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
