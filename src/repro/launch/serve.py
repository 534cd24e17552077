"""Serving driver CLI: continuous batching + ReuseSense decode.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
        --requests 8 --batch-slots 4 --max-new 24 --reuse

Runs the full serving stack: prefill into slot lanes, shared decode step with
the reuse engine threaded, per-site similarity stats printed at the end (the
live analogue of paper Fig. 12's per-layer similarity). `--reduced` shrinks
every width to smoke-test scale; `--layers N` keeps the published widths and
cuts only the depth, which is how a large model fits one chip:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --layers 8 \
        --reuse --batch-slots 8 --prompt-len 128 --cache-len 2048 \
        --check-kernels

The reuse sites run on the best compiled kernel substrate of the process
(`kernels/backend.py`): the Pallas kernels on a TPU, the compiled-XLA tier on
a CPU. `--check-kernels` makes that a contract: the run fails unless the
substrate is compiled Pallas, no site resolves to the reference GEMM, and the
compiled decode step carries the delta and reuse-GEMM kernels.

Observability (`repro.obs`): `--obs` turns on span tracing + metrics for the
run; `--obs-dir OUT` additionally exports `metrics.prom` (Prometheus
textfile), `metrics.jsonl` (snapshots for `python -m repro.obs.top`),
`spans.jsonl`, and `latency_table.json` — the measured per-(site, layer,
exec_path) dispatch latencies, probed at the run's measured skip rates. Feed
that table back with `--latency-table` (or to `repro.tune.fit
--latency-table`) and break-even/exec decisions are priced from measured
wall-clock instead of cost-model constants. `--profile-dir` opens a
`jax.profiler` device-trace window around the serve loop; the obs spans'
TraceAnnotations line up host spans with device slices.

Fault containment (`repro.guard`): with `--control-every` the controller
carries a QuarantineBreaker — array sentinels ride the ctrl snapshot, tripped
lanes are pinned to basic/dense and scrubbed, transitions land in the
decision journal as `kind="quarantine"` rows. `--inject <scenario[:k=v,...]>`
arms a deterministic fault (see `repro.guard.inject.SCENARIOS`: poison-nan,
poison-sim, ctrl-garbage, poison-counters, lying-telemetry, torn-journal,
corrupt-ckpt, stall) at the real seams, so a chaos run exercises the exact
production wiring. Each decode step is timed; the straggler watchdog feeds
stall events into the same breaker.
"""

from __future__ import annotations

import argparse
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import events, trace as obs_trace

from repro.configs import get_config
from repro.core.reuse_cache import cache_bytes, resolve_exec_path
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.scheduler import ContinuousBatcher, Request, reset_slot
from repro.serve.serve_step import (
    build_reuse_engine,
    greedy_sample,
    init_serve_state,
    jit_decode,
    jit_prefill,
)
from repro.models import init_params


def require_pallas(engine, hlo: str) -> dict[str, int]:
    """Fail unless the compiled decode step runs the reuse sites on compiled
    Pallas: the substrate is Pallas, every site resolves to a kernel path
    (never the reference GEMM of `kernels/ref.py` or the jnp gather), and the
    HLO carries the fused delta kernel plus each site's GEMM kernel. Returns
    the Pallas kernel call counts found in the HLO."""
    from repro.kernels import backend
    from repro.roofline.hlo_parse import pallas_kernel_calls

    sub = backend.for_impl(engine.impl)
    if sub is not backend.PALLAS:
        raise RuntimeError(
            f"reuse substrate is {sub.name!r}, not compiled Pallas "
            f"({backend.describe()})")
    want = {"delta_quant"}
    for name, spec in engine.sites.items():
        path = resolve_exec_path(spec, engine.impl)
        if path == "kernel":
            want.add(f"reuse_matmul_{spec.dataflow}")
        elif path == "ragged":
            want.add("reuse_matmul_ragged")
        else:
            raise RuntimeError(
                f"site {name!r} resolves to exec_path {path!r}, which runs "
                "no Pallas kernel")
    calls = pallas_kernel_calls(hlo)
    missing = sorted(want - set(calls))
    if missing:
        raise RuntimeError(
            f"compiled decode step lacks Pallas kernels {missing}; "
            f"found {calls}")
    return calls


def main(argv: list[str] | None = None) -> dict[str, Any]:
    """Run the CLI on `argv` (default: the process arguments). Returns the
    serve loop's wall seconds and, with --check-kernels, the Pallas kernel
    counts of the compiled decode step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers of the config: "
                    "depth is cut, every width stays as published (one "
                    "chip's share of a model that needs several)")
    ap.add_argument("--check-kernels", action="store_true",
                    help="compile the decode step before serving and fail "
                    "unless its reuse sites run the compiled Pallas kernels "
                    "(requires --reuse)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reuse", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sensor-jsonl", default=None,
                    help="append the final SensorReport rows to this JSONL file")
    ap.add_argument("--tuned-policy", default=None,
                    help="tuned-table JSON (python -m repro.tune.fit output); "
                    "replaces the global-constant policy with per-site "
                    "tunables and reports tuned-vs-default mode deltas")
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="re-run the host-side mode policy every N decode "
                    "steps (0 = keep registration-time modes); superseded "
                    "by --control-every, which runs the full adaptive "
                    "control plane at that cadence instead")
    ap.add_argument("--affinity", action="store_true",
                    help="place requests on slots by predicted stream "
                    "similarity (per-slot sim_ema affinity) instead of "
                    "first-free")
    ap.add_argument("--control-every", type=int, default=0,
                    help="run the online control plane (repro.control) every "
                    "N decode steps: live per-site retuning, overflow-driven "
                    "max_active_k budget adaptation, and learned per-session "
                    "admission (replaces the synthetic predicted_sim). "
                    "Subsumes --refresh-every (the controller invokes the "
                    "mode refresh itself).")
    ap.add_argument("--control-journal", default=None,
                    help="append the controller's decision journal (JSONL) "
                    "to this path for audit/replay")
    ap.add_argument("--obs", action="store_true",
                    help="enable the observability plane: perf_counter spans "
                    "around serve steps/prefills, correlation ids stamped on "
                    "sensor/journal rows, metrics aggregation")
    ap.add_argument("--replica-id", default=None,
                    help="fleet replica identity: stamp every emitted row's "
                    "trace block with replica=ID so a fleet aggregator "
                    "(repro.obs.fleet) can join this replica's streams; "
                    "unset, emission is byte-identical to before")
    ap.add_argument("--obs-dir", default=None,
                    help="export observability artifacts here (implies "
                    "--obs): metrics.prom, metrics.jsonl, spans.jsonl, and "
                    "latency_table.json (measured per-site/path dispatch "
                    "latencies, probed at the run's measured skip rates)")
    ap.add_argument("--profile-dir", default=None,
                    help="open a jax.profiler trace window around the serve "
                    "loop, writing the device trace here")
    ap.add_argument("--latency-table", default=None,
                    help="measured latency table (a previous run's "
                    "--obs-dir/latency_table.json) for the online controller "
                    "— break-even/exec retunes are priced from measured "
                    "wall-clock; requires --control-every")
    ap.add_argument("--cache-ckpt", default=None,
                    help="reuse-cache checkpoint directory: restore the "
                    "latest step at start (ctrl-block precedence: checkpoint "
                    "< tuned table < live controller, resolutions journaled) "
                    "and save the final cache at exit; requires --reuse")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="shard the reuse serve across a device mesh "
                    "(repro.launch.mesh specs: 'host:N' puts N devices of "
                    "this host on the model axis — the host's chips, or on "
                    "a CPU host N devices forced with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N — "
                    "'host:N@S' makes the model axis S wide, 'prod' the "
                    "16x16 pod). The reuse cache is sharded along the model "
                    "axis with the weights it shadows; skip decisions stay "
                    "shard-local (compiled step is asserted gather-free on "
                    "cache buffers at startup) and sensor counters cross the "
                    "mesh once per control window; requires --reuse")
    ap.add_argument("--inject", default=None, metavar="SCENARIO[:k=v,...]",
                    help="arm a deterministic fault scenario "
                    "(repro.guard.inject.SCENARIOS) at the production seams "
                    "— e.g. poison-nan:at_step=12,site=mlp_up — for chaos "
                    "runs; requires --reuse")
    args = ap.parse_args(argv)

    for flag in ("sensor_jsonl", "tuned_policy", "refresh_every", "affinity",
                 "control_every", "control_journal", "cache_ckpt", "inject",
                 "mesh", "check_kernels"):
        if getattr(args, flag) and not args.reuse:
            ap.error(f"--{flag.replace('_', '-')} requires --reuse")
    if args.control_journal and not args.control_every:
        ap.error("--control-journal requires --control-every")
    if args.latency_table and not args.control_every:
        ap.error("--latency-table requires --control-every")
    if args.control_every and args.refresh_every:
        print("--control-every supersedes --refresh-every "
              "(the controller runs the mode refresh itself)")
        args.refresh_every = 0
    print(f"compile cache: {enable_compile_cache()}")

    obs_on = args.obs or bool(args.obs_dir)
    registry = None
    if obs_on:
        from repro.obs.metrics import MetricsRegistry

        obs_trace.enable()
        run_id = events.new_run_id()
        events.set_ids(run=run_id)
        registry = MetricsRegistry()
        print(f"obs: tracing enabled, run={run_id}")
    if args.replica_id:
        # works with or without --obs: stamp() fires whenever any id is set,
        # so even a journal/sensor-only run carries its replica identity
        events.set_ids(replica=args.replica_id)
        print(f"obs: replica={args.replica_id}")

    # One shared journal: the restore-precedence pass (below) and the online
    # controller append to the same audit stream.
    journal = None
    if args.control_journal:
        from repro.control.report import DecisionJournal

        journal = DecisionJournal(args.control_journal)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        print(f"config: {cfg.name} cut from {cfg.n_layers} to {args.layers} "
              f"layers; widths unchanged")
        cfg = cfg.with_layers(args.layers)
    assert cfg.family != "audio", "encoder archs have no decode path"

    rng = np.random.default_rng(args.seed)
    mesh = None
    if args.mesh:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import parse_mesh_spec

        mesh = parse_mesh_spec(args.mesh)
        # params and decode state replicate (GSPMD partitions the step
        # around the committed input shardings); params are drawn on the
        # mesh directly, so no device ever holds a second copy
        replicated = NamedSharding(mesh, PartitionSpec())
        params = jax.jit(init_params, static_argnums=0,
                         out_shardings=replicated)(
            cfg, jax.random.PRNGKey(args.seed))
        state = jax.device_put(
            init_serve_state(cfg, args.batch_slots, args.cache_len),
            replicated)
    else:
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        state = init_serve_state(cfg, args.batch_slots, args.cache_len)

    engine = None
    rcache = None
    if args.reuse:
        policy = None
        if args.tuned_policy:
            from repro.tune.table import load_tuned_policy

            policy = load_tuned_policy(args.tuned_policy)
            print(f"tuned policy: {len(policy.site_tunables)} site entries "
                  f"from {args.tuned_policy}")
        engine = build_reuse_engine(cfg, impl="pallas", policy=policy)
        if mesh is not None:
            from repro.launch.mesh import mesh_axes

            ax = mesh_axes(mesh)
            planned = engine.shard_sites(ax["model_size"], mesh=mesh)
            print(f"mesh: {dict(mesh.shape)} — {len(planned)} sites sharded "
                  f"{ax['model_size']}-way on the model axis")
        rcache = engine.init_cache(args.batch_slots)
        if mesh is not None:
            from repro.dist.shard import cache_shardings

            # cache shards live WITH the weight columns they shadow
            rcache = jax.device_put(
                rcache, cache_shardings(engine, mesh, rcache))
        from repro.kernels import backend as kernel_backend

        print(f"kernel substrate: {kernel_backend.describe()}")
        print(f"reuse cache: {cache_bytes(rcache)/1e6:.2f} MB "
              f"({len(engine.sites)} sites)")
        if args.cache_ckpt:
            from repro.ckpt.checkpoint import latest_step, restore_checkpoint
            from repro.control.restore import resolve_restored_ctrl

            ck_step = latest_step(args.cache_ckpt)
            if ck_step is not None:
                rcache = restore_checkpoint(args.cache_ckpt, ck_step, rcache)
                resolutions = resolve_restored_ctrl(
                    engine, rcache, journal=journal, step=0)
                print(f"cache checkpoint: restored step {ck_step} from "
                      f"{args.cache_ckpt}; ctrl precedence resolved "
                      f"{len(resolutions)} lanes "
                      f"(checkpoint < tuned table < live)")
                for d in resolutions:
                    where = d.site + (f"@{d.layer}" if d.layer is not None
                                      else "")
                    print(f"  restore {where} {d.field}: "
                          f"{d.before} -> {d.after}")
        if args.tuned_policy:
            # tuned-vs-default delta: probe each site at full similarity
            # (isolates the min-work admission decision) and report the
            # per-site knobs that moved off the global constants
            from repro.core.policy import ReusePolicy

            default = ReusePolicy()
            for name, spec in engine.sites.items():
                t = engine.policy.resolve(name)
                d_mode = default.decide_mode(spec, 1.0)
                t_mode = engine.policy.decide_mode(spec, 1.0)
                moved = (d_mode != t_mode
                         or abs(t.sim_threshold - default.sim_threshold) > 1e-9
                         or t.block_k is not None
                         or t.exec_path is not None)
                if moved:
                    budget = (f"@{spec.max_active_k}"
                              if spec.max_active_k is not None else "")
                    print(f"  tuned delta {name}: mode@sim=1 {d_mode}->"
                          f"{t_mode} thr={t.sim_threshold:.3f} "
                          f"block_k={spec.block_k} "
                          f"exec={spec.exec_path}{budget}")

    # Batched-prefill simplification: slot prefill re-runs the batch prefill
    # with the slot's prompt in its lane (a production server runs a separate
    # prefill worker; the KV-lane insertion is what matters here).
    prefill_jit = jit_prefill(cfg)

    # Jitted decode-step variants, keyed by the registered sites' full spec
    # signature (exec paths, budgets, tile geometry — everything the closure
    # bakes into the trace). A controller flip to a previously-seen operating
    # point reuses its compiled executable instead of retracing from scratch;
    # mode flips are ctrl-array writes and never change the key. The serving
    # state and the reuse cache are DONATED through the step: the previous
    # step's buffers are dead the moment the call is issued, so XLA writes
    # the new caches in place instead of allocating a copy per token.
    decode_variants: dict[tuple, Any] = {}

    def spec_signature() -> tuple:
        if engine is None:
            return ()
        return tuple(sorted(engine.sites.items()))

    def jit_decode_factory():
        key = spec_signature()
        fn = decode_variants.get(key)
        if fn is None:
            decode_variants[key] = fn = jit_decode(cfg, engine)
        return fn

    decode_jit = jit_decode_factory()

    kernel_calls = None
    if mesh is not None or args.check_kernels:
        # Checked once at startup against the compiled (post-SPMD) step.
        aval = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding))
        tok_aval = jax.ShapeDtypeStruct((args.batch_slots, 1), jnp.int32)
        hlo = decode_jit.lower(
            aval(params), tok_aval, aval(state), aval(rcache)
        ).compile().as_text()
    if args.check_kernels:
        kernel_calls = require_pallas(engine, hlo)
        print(f"kernel check: OK — decode step runs Pallas {kernel_calls}")
    if mesh is not None:
        # The sharded-serving hot-path invariant: no all-gather/all-to-all in
        # the donated serve step may touch a reuse-cache buffer (shard-local
        # quantize→delta→mask→skip; the once-per-window counter all-reduce
        # rides the ctrl snapshot, not this step).
        from repro.dist.shard import cache_shape_signatures
        from repro.roofline.hlo_parse import (
            cache_collective_violations,
            parse_collective_bytes,
        )

        violations = cache_collective_violations(
            hlo, cache_shape_signatures(rcache))
        if violations:
            raise RuntimeError(
                "sharded serve step gathers reuse-cache state across the "
                f"mesh — hot-path invariant violated: {violations}")
        coll = parse_collective_bytes(hlo)
        print(f"hlo no-gather check: OK — 0 cache-touching gathers "
              f"({coll['count']} collectives, "
              f"{coll['total_bytes']/1e3:.1f} KB/device in compiled step)")

    sstate = {"state": state, "rcache": rcache}

    # Fault plane: the armed injector (chaos runs) plus the step clock the
    # straggler watchdog reads. Armed independently of the control plane — a
    # poisoned run WITHOUT the breaker is the useful negative control.
    injector = None
    watchdog = None
    if args.inject:
        from repro.guard import FaultInjector

        injector = FaultInjector.from_spec(args.inject)
        print(f"fault injection armed: {injector.scenario} "
              f"{injector.params} site={injector.site} "
              f"layer={injector.layer}")
    if engine is not None:
        from repro.guard import StragglerWatchdog

        watchdog = StragglerWatchdog()

    # Learned admission + online control plane (repro.control): the predictor
    # learns per-session similarity from retirement telemetry, the controller
    # retunes the policy / adapts budgets from live counters on a cadence.
    predictor = None
    controller = None
    breaker = None
    if args.control_every > 0:
        from repro.control import AdmissionPredictor, ControlConfig, Controller

        latency = None
        if args.latency_table:
            from repro.obs.latency import load_latency_table, table_provenance

            latency = load_latency_table(args.latency_table)
            print(f"controller pricing from measured latencies: "
                  f"{args.latency_table} ({len(latency)} rows)")
            prov = table_provenance(latency)
            if prov != "compiled":
                print(f"WARNING: latency table {args.latency_table} carries "
                      f"{prov} measurements — interpret-mode numbers run "
                      "20-80x off compiled reality; re-probe with a compiled "
                      "serve run (--obs-dir) before trusting its pricing")
                if journal is not None:
                    journal.note(
                        note="latency_table_provenance",
                        path=args.latency_table, provenance=prov,
                        meta=latency.meta,
                    )
        predictor = AdmissionPredictor()
        # the guard plane rides the controller cadence: sentinels are read
        # from the same ctrl snapshot, containment decisions land in the
        # same journal stream, and the breaker's probation clock ticks in
        # control intervals
        from repro.guard import QuarantineBreaker

        breaker = QuarantineBreaker()
        controller = Controller(
            ControlConfig(),
            admission=predictor,
            journal=journal,
            latency=latency,
            guard=breaker,
        )

    def prefill_fn(prompt, slot):
        nonlocal sstate
        full = jnp.zeros((args.batch_slots, prompt.shape[1]), jnp.int32)
        full = full.at[slot].set(jnp.asarray(prompt[0]))
        logits, new_state = prefill_jit(params, full, sstate["state"])
        # only this slot's lanes changed meaningfully; adopt the new caches.
        # No admission= here: the scheduler's on_place hook has ALREADY bound
        # the slot to the incoming session (admission order: pick slot ->
        # on_place -> prefill), and the retirement-path reset below is where
        # the departing occupant's predictor state gets cleared.
        sstate["state"] = new_state
        sstate["rcache"] = reset_slot(sstate["rcache"], slot)
        return int(greedy_sample(logits[slot: slot + 1, -1:])[0, 0])

    step_clock = {"step": 0}

    def decode_fn(tokens):
        nonlocal sstate
        step_clock["step"] += 1
        t0 = obs_trace.now()
        if injector is not None:
            # the stall scenario lives INSIDE the timed region — exactly
            # where a straggler host's slowness would land
            injector.maybe_stall(step_clock["step"])
        logits, new_state, new_rcache = decode_jit(
            params, jnp.asarray(tokens), sstate["state"], sstate["rcache"]
        )
        sstate["state"] = new_state
        sstate["rcache"] = new_rcache
        out = np.asarray(greedy_sample(logits[:, -1:]))[:, :, 0] \
            if logits.ndim == 4 else np.asarray(greedy_sample(logits))
        # np.asarray above forced the device sync, so dt is real step time
        if watchdog is not None:
            event = watchdog.observe(step_clock["step"], obs_trace.now() - t0)
            if event is not None:
                print(f"straggler: step {event['step']} took "
                      f"{event['seconds']:.3f}s vs median "
                      f"{event['median']:.3f}s")
                if breaker is not None:
                    breaker.note_stall(event)
        return out

    telemetry_fn = None
    on_retire = None
    if engine is not None:
        from repro.sensor.aggregate import slot_telemetry

        def telemetry_fn(slot):
            return slot_telemetry(engine, sstate["rcache"], slot)

        def on_retire(req):
            t = req.telemetry
            if predictor is None:
                # lane store for the synthetic --affinity path only; with
                # the control plane, predictor.lane_character is THE store
                lane_sim[req.slot] = t["hit_rate"]
            else:
                # learn BEFORE the reset clears the slot binding
                predictor.observe_retirement(req)
            print(f"SensorReport rid={req.rid} slot={t['slot']} "
                  f"steps={t['steps']} hit_rate={t['hit_rate']:.3f} "
                  f"sites={t['n_sites']}")
            # Reset the freed lane now (telemetry is already snapshotted):
            # bounds how much idle-slot decode history leaks into the
            # end-of-run report before the next admission resets again.
            sstate["rcache"] = reset_slot(sstate["rcache"], req.slot,
                                          admission=predictor)

    slot_sim_fn = None
    on_step = None
    # Lane similarity history for affinity placement. Freed lanes are reset
    # (their live sim_ema is zero by the time a new request is admitted), so
    # the lane's "character" is the retirement-telemetry hit rate of the last
    # stream that lived there — snapshotted before the reset.
    lane_sim: dict[int, float] = {}
    if engine is not None and args.affinity:
        def slot_sim_fn(slot):
            return lane_sim.get(slot, 0.0)

    if engine is not None and args.refresh_every > 0:
        def on_step(step_idx):
            nonlocal decode_jit
            if step_idx % args.refresh_every == 0:
                changed = engine.refresh_modes(sstate["rcache"])
                if engine.last_mode_events:
                    # per-layer kernelMode flips are ctrl-array writes — the
                    # traced step branches on the cache, so NO rebuild here
                    flips = ", ".join(
                        f"{e['site']}"
                        + (f"@{e['layer']}" if e["layer"] is not None else "")
                        + f"->{e['after']}"
                        for e in engine.last_mode_events)
                    print(f"mode refresh @step {step_idx}: {flips}")
                if changed:
                    # exec-path flips ARE spec changes baked into the traced
                    # step — a fresh trace (the paper's CRS re-invocation)
                    decode_jit = jit_decode_factory()
                    print(f"exec refresh @step {step_idx}: {changed}")

    predict_sim_fn = None
    on_place = None
    if controller is not None:
        # learned admission supplies predictions + lane affinity; per-slot
        # predictor state is cleared on recycle by reset_slot(admission=...)
        predict_sim_fn = predictor.predict
        slot_sim_fn = predictor.slot_affinity
        on_place = predictor.on_placed

        def on_step(step_idx):
            nonlocal decode_jit
            if step_idx % args.control_every == 0:
                # the window id joins this interval's journal rows with the
                # spans and sensor rows emitted while it was open
                with events.context(window=step_idx):
                    rep = controller.step(
                        engine, sstate["rcache"], step=step_idx)
                if registry is not None:
                    from repro.obs.metrics import (
                        observe_control_report,
                        observe_guard_report,
                    )

                    observe_control_report(registry, rep)
                    if controller.last_guard_report is not None:
                        observe_guard_report(
                            registry, controller.last_guard_report)
                if rep.decisions:
                    print("\n".join(rep.summary_lines()))
                if rep.changed:
                    # live spec/mode changes are baked into the traced step
                    decode_jit = jit_decode_factory()

    if injector is not None:
        # chain the injector through the production seams: cache poisoning
        # lands post-decode (before the controller's next look), forged
        # telemetry rides the real retirement path
        base_on_step, base_telemetry = on_step, telemetry_fn

        def on_step(step_idx):
            n_fired = len(injector.fired)
            sstate["rcache"] = injector.on_cache_update(
                sstate["rcache"], step_idx)
            if len(injector.fired) > n_fired:
                print(f"inject @step {step_idx}: "
                      f"{injector.fired[-1]['detail']}")
            if base_on_step is not None:
                base_on_step(step_idx)

        if base_telemetry is not None:
            def telemetry_fn(slot):
                return injector.on_telemetry(
                    base_telemetry(slot), step_clock["step"])

    batcher = ContinuousBatcher(
        batch_slots=args.batch_slots,
        prefill_fn=prefill_fn,
        decode_fn=decode_fn,
        max_steps=args.requests * args.max_new + 8,
        telemetry_fn=telemetry_fn,
        on_retire=on_retire,
        slot_sim_fn=slot_sim_fn,
        on_step=on_step,
        predict_sim_fn=predict_sim_fn,
        on_place=on_place,
    )
    for i in range(args.requests):
        batcher.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=(args.prompt_len,), dtype=np.int32),
            max_new_tokens=args.max_new,
            # Without the control plane, a synthetic stand-in predictor:
            # traffic alternates sticky-looking and one-shot-looking streams.
            # With it, predictions come from the LEARNED per-session
            # estimator (predict_sim_fn) instead of being caller-trusted.
            predicted_sim=(0.8 if i % 2 == 0 else 0.2)
            if (args.affinity and controller is None) else None,
            # two synthetic session classes so the predictor has sessions
            # to learn: even rids are the "sticky" session, odd the one-shot
            session=f"sess-{i % 2}" if controller is not None else None,
        ))

    if args.profile_dir:
        obs_trace.start_profile(args.profile_dir)
    t0 = obs_trace.now()  # perf_counter: monotonic wall-clock discipline
    done = batcher.run()
    dt = obs_trace.now() - t0
    if args.profile_dir:
        print(f"device trace written to {obs_trace.stop_profile()}")
    print(f"served {len(done)}/{args.requests} requests in {dt:.2f}s; "
          f"{batcher.stats}")
    report = None
    if engine is not None:
        report = engine.sensor_report(sstate["rcache"])
        print("\n".join(report.summary_lines()))
        if engine.shards:
            # per-shard skip rates from one final cross-mesh snapshot (the
            # same [S] lanes the controller journals per window)
            snap = engine.ctrl_snapshot(sstate["rcache"])
            for name in sorted(engine.shards):
                s = snap.get(name, {})
                if "skipped_shard" not in s:
                    continue
                sk = np.asarray(s["skipped_shard"], np.float64)
                cp = np.asarray(s["computed_shard"], np.float64)
                rates = sk / np.maximum(sk + cp, 1e-9)
                print(f"shard skip {name}: " + " ".join(
                    f"s{i}={r:.3f}" for i, r in enumerate(rates)))
            print(f"ici traffic: reduce={engine.ici_reduce_bytes/1e3:.1f} KB "
                  f"ctrl-writes={engine.ici_write_bytes/1e3:.1f} KB "
                  f"(priced at E_ICI in the sensor energy report)")
        if args.sensor_jsonl:
            report.write_jsonl(args.sensor_jsonl)
            print(f"sensor report appended to {args.sensor_jsonl}")
    if controller is not None:
        n_dec = sum(len(r.decisions) for r in controller.reports)
        print(f"control plane: {len(controller.reports)} intervals, "
              f"{n_dec} decisions, admission {predictor.stats()}")
        if controller.journal is not None:
            print(f"decision journal: {controller.journal.rows_written} rows "
                  f"-> {controller.journal.path}")
    if breaker is not None:
        states = breaker.lane_states()
        lanes = ", ".join(
            f"{s}" + (f"@{l}" if l is not None else "") + f"={st}"
            for (s, l), st in sorted(states.items(),
                                     key=lambda kv: (kv[0][0], kv[0][1] or 0)))
        print(f"guard plane: {breaker.total_trips} sentinel trips, "
              f"{breaker.stall_windows} stall windows, "
              f"{breaker.quarantined_lanes()} lanes quarantined"
              + (f" [{lanes}]" if lanes else ""))
    if args.cache_ckpt and engine is not None:
        from repro.ckpt.checkpoint import save_checkpoint

        save_checkpoint(args.cache_ckpt, batcher.stats["steps"],
                        sstate["rcache"])
        print(f"cache checkpoint: saved step {batcher.stats['steps']} "
              f"to {args.cache_ckpt}")
    if injector is not None:
        # at-rest scenarios fire at exit, against the artifacts just written
        if args.control_journal:
            injector.tear_journal(args.control_journal)
        if args.cache_ckpt:
            injector.corrupt_checkpoint(args.cache_ckpt)
        print(f"fault injection: {len(injector.fired)} fault(s) fired")
        for ev in injector.fired:
            print(f"  {ev['scenario']} @step {ev['step']}: {ev['detail']}")
    if args.obs_dir:
        from repro.obs.export import write_jsonl, write_prometheus
        from repro.obs.metrics import observe_sensor_report, observe_spans

        os.makedirs(args.obs_dir, exist_ok=True)
        if engine is not None:
            # Probe measured dispatch latency per (site, exec_path), at the
            # run's MEASURED skip rates — the table --latency-table and
            # `repro.tune.fit --latency-table` consume.
            from repro.obs.latency import probe_latency_table

            skips = {s.site: s.tile_skip_rate for s in report.per_site}
            table = probe_latency_table(
                engine, args.batch_slots, skip_rates=skips)
            lat_path = os.path.join(args.obs_dir, "latency_table.json")
            table.save(lat_path, meta={"arch": args.arch})
            print("\n".join(table.summary_lines()))
            print(f"measured latency table -> {lat_path}")
            observe_sensor_report(registry, report)
        observe_spans(registry, obs_trace.spans())
        n = write_prometheus(
            os.path.join(args.obs_dir, "metrics.prom"), registry)
        write_jsonl(os.path.join(args.obs_dir, "metrics.jsonl"), registry)
        n_spans = obs_trace.write_spans_jsonl(
            os.path.join(args.obs_dir, "spans.jsonl"))
        print(f"obs exports -> {args.obs_dir} (metrics.prom {n} lines, "
              f"metrics.jsonl, spans.jsonl {n_spans} spans)")
    assert len(done) == args.requests
    return {"seconds": dt, "kernel_calls": kernel_calls}


if __name__ == "__main__":
    main()
