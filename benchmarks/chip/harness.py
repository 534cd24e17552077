"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is data found by name: `configs/<config>.json` (whose published
`model.model_type` names its architecture module `arch/<model_type>.py`),
`traffic/<traffic>.json` (whose `kind` names `generators/<kind>.py`),
`limits/<cell>.json` and `metrics/<metric>.py`. This file holds no branch on
any of those names.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from chip import arch

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NoChip(SystemExit):
    """The run cannot measure here; exits non-zero with no result."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """A traffic generator or metric reader, by file: a metric's name may
    hold a dot."""
    name = f"chip.{path.parent.name}.{path.stem}".replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


@dataclasses.dataclass
class Cell:
    name: str
    conf: dict
    family: object         # the configuration's architecture module
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(bench: dict, workload: str, data_dir: pathlib.Path) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = load_json(data_dir / "configs" / f"{w['config']}.json")

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(
        name=workload,
        conf=conf,
        family=arch.resolve(conf["model"]),
        traffic=load_json(data_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(data_dir / "limits" / f"{workload}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if here(m)],
        per_layer=[m for m in bench["per_layer"] if here(m)],
    )


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    from chip.peaks import peak_for

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise NoChip(f"no TPU: JAX found {info['platform']!r} devices")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
        try:
            peak_for(info["kind"])
        except KeyError as e:
            raise NoChip(str(e)) from None
    return info


def enable_persistent_cache() -> None:
    """The program's persistent compile cache (`$JAX_COMPILATION_CACHE_DIR`,
    else `.jax_cache/` at the checkout root), holding every program however
    quick it compiles, so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Programs traced, compiled or loaded from the persistent cache, from
    JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    system: object
    window: object
    trace: object          # trace_reduce.Trace, or None
    shape: object          # the architecture module's shape(model)
    peak: object           # peaks.Peak, or None off the chip
    tiles: dict            # site -> (skipped, computed) over the window


def end_to_end(window, setup_s: float) -> dict:
    gaps = np.asarray(window.gaps_s) * 1e3
    return {
        "tokens_per_s": window.tokens / window.seconds,
        "itl_p50_ms": float(np.percentile(gaps, 50)) if gaps.size
        else math.nan,
        "itl_p95_ms": float(np.percentile(gaps, 95)) if gaps.size
        else math.nan,
        "setup_s": setup_s,
    }


def window_summary(window) -> str:
    """Where the window's host time went: the steps of each kind that
    ended inside it, their longest, and the time between steps."""
    close = window.start + window.seconds
    steps = [s for s in window.steps if s.end <= close]
    parts = []
    for kind in sorted({s.kind for s in steps}):
        d = [s.end - s.start for s in steps if s.kind == kind]
        parts.append(f"{kind} {len(d)} steps {sum(d):.4f} s "
                     f"(longest {max(d) * 1e3:.2f} ms)")
    between = [b.start - a.end for a, b in zip(steps, steps[1:])]
    if between:
        parts.append(f"between steps {sum(between):.4f} s "
                     f"(longest {max(between) * 1e3:.2f} ms)")
    return "window: " + "; ".join(parts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             fault=None) -> dict:
    """One run. `fault` (tests only) wraps the built system to break the
    timed path; `require_chip=False` (tests only) runs on any backend."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.profiler

    from chip import check, weights
    from chip import system as system_mod
    from chip.peaks import peak_for

    info = device_info(cell.chips, require_chip)
    if require_chip:
        enable_persistent_cache()
    compiles = CompileCount()

    model, family = cell.conf["model"], cell.family
    params = weights.make_params(family.layout(model), seed)
    jax.block_until_ready(params)
    sut = system_mod.build(cell.conf, family, cell.traffic, params,
                           check_kernels=require_chip)
    if fault is not None:
        fault(sut)
    gen = load_module(HERE / "generators" / f"{cell.traffic['kind']}.py"
                         ).Generator(sut, cell.traffic, seed)
    gen.warm_up()
    tiles0 = sut.counters()
    # Set-up's objects leave the collector's generations, as serving engines
    # freeze their start-up heap, so that a full collection inside the
    # window does not scan them again.
    gc.collect()
    gc.freeze()

    trace_dir = ROOT / ".bench_trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    before = compiles.count
    with jax.profiler.TraceAnnotation("bench:window"):
        window = gen.run(seconds)
    in_window = compiles.count - before
    tiles1 = sut.counters()
    if trace:
        jax.profiler.stop_trace()
        scopes = sut.scopes()
    gen.drain(window)
    peak_bytes = memory_peak_bytes()
    print(f"device: {info['kind']} x{info['count']} ({info['platform']}); "
          f"memory_peak_bytes {peak_bytes}; Pallas kernels in the decode "
          f"step {sut.kernel_calls}; compilations inside the window "
          f"{in_window}", flush=True)
    print(window_summary(window), flush=True)

    # ------------------------------------------------------------ the check
    sut.free()
    gc.collect()
    spec = family.spec_of(model)
    picked = check.sample(window.requests, cell.traffic["check_requests"],
                          seed)
    limits = {n: float(v["limit"]) for n, v in cell.limits.items()}
    numbers = check.compared(cell.conf, limits, family, spec, params, picked)
    numbers["compilations_in_window"] = in_window
    limits["compilations_in_window"] = 0
    n_tok = sum(len(r.tokens) for r in picked)
    correct = bool(picked) and all(numbers[n] <= limits[n] for n in limits)

    # ------------------------------------------------------------ metrics
    result: dict = {"correct": correct, "attempted": window.attempted,
                    "failed": 0}
    tiles = {k: (tiles1[k][0] - tiles0[k][0], tiles1[k][1] - tiles0[k][1])
             for k in tiles1}
    if not trace:
        e2e = end_to_end(window, setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from chip import trace_reduce

        tr = trace_reduce.load(trace_reduce.find(str(trace_dir)), scopes)
        ctx = Context(cell=cell, system=sut, window=window, trace=tr,
                      shape=family.shape(model),
                      peak=peak_for(info["kind"]) if require_chip else None,
                      tiles=tiles)
        result["metrics"] = {}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        w = tr.window()
        busy = tr.busy_ns(w.start, w.end) if w else 0.0
        info["busy_s"] = busy * 1e-9
        info["window_s"] = (w.end - w.start) * 1e-9 if w else 0.0
        if w:
            result["breakdown"] = {
                "device_ops": tr.top_ops(w.start, w.end),
                "idle_gaps": tr.idle_gaps(w.start, w.end)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    info["memory_peak_bytes"] = peak_bytes
    result["device"] = info
    result["check"] = {n: {"value": numbers[n], "limit": limits[n]}
                       for n in limits}
    print(f"check: {len(picked)} requests, {n_tok} served tokens",
          file=sys.stderr)
    for n in limits:
        print(f"check: {n} {numbers[n]!r} (limit {limits[n]!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return result
