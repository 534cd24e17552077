"""In-process N-replica serving harness — the fleet plane's test substrate.

    PYTHONPATH=src python -m repro.launch.replicas --arch qwen3-32b --reduced \
        --replicas 2 --out /tmp/fleet --inject poison-sim:at_step=24

Runs N *independent* serving replicas in one process: each replica owns its
engine, reuse cache, serving state, continuous batcher, control plane
(controller + admission predictor + quarantine breaker), decision journal,
metrics registry, and obs dir — exactly the per-process state a real fleet
member owns — while sharing the (read-only) model parameters. The driver
interleaves them round-robin via `ContinuousBatcher.step_once`, wrapping
every replica turn in `events.context(run=..., replica=...)` so each row in
each stream carries its (run, replica) join keys, and drains the span buffer
after each turn so span attribution follows the same boundary.

Each replica gets a DISTINCT session mix (replica i cycles `2 + i` session
identities), so admission predictors learn different traffic and the fleet
view has real variance to show. `--inject` arms one replica (default: the
last) with a deterministic fault from `repro.guard.inject` — the chaos case
the SLO watcher must attribute to THAT replica and no other.

While the replicas run, a `FleetAggregator` tails all the obs dirs live
(the same code path an out-of-process aggregator would use) and an
`SLOWatcher` evaluates after every poll. Outputs under `--out`:

    replica-<id>/{sensor,journal,spans,metrics}.jsonl + metrics.prom
    fleet_report.json    per-replica + fleet rollup (obs.fleet schema)
    alerts.jsonl         SLO alert rows (journal-style)
    fleet.prom           fleet_* gauges + fleet_alerts_total counters

This harness is the scaffold the PR-10 router will place sessions onto: the
`ReplicaHealth` it surfaces per replica is the placement signal set the
ROADMAP assigns the router.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import events, trace as obs_trace
from repro.obs.metrics import (
    MetricsRegistry,
    observe_control_report,
    observe_guard_report,
    observe_sensor_report,
    observe_spans,
)

from repro.configs import get_config
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


class Replica:
    """One serving replica's full per-process state, obs dir included."""

    def __init__(self, name: str, cfg, params, args, fleet_dir: str, *,
                 injector=None, seed: int = 0):
        from repro.control import AdmissionPredictor, ControlConfig, Controller
        from repro.control.report import DecisionJournal
        from repro.guard import QuarantineBreaker

        self.name = name
        self.cfg = cfg
        self.params = params
        self.injector = injector
        self.run = events.new_run_id()
        self.obs_dir = os.path.join(fleet_dir, f"replica-{name}")
        os.makedirs(self.obs_dir, exist_ok=True)
        self.sensor_path = os.path.join(self.obs_dir, "sensor.jsonl")
        self.spans_path = os.path.join(self.obs_dir, "spans.jsonl")
        self.metrics_path = os.path.join(self.obs_dir, "metrics.jsonl")

        self.engine = build_reuse_engine(cfg, impl="pallas")
        self.registry = MetricsRegistry()
        self.journal = DecisionJournal(
            os.path.join(self.obs_dir, "journal.jsonl"))
        self.predictor = AdmissionPredictor()
        self.breaker = QuarantineBreaker()
        self.controller = Controller(
            ControlConfig(), admission=self.predictor, journal=self.journal,
            guard=self.breaker)
        self.sstate = {
            "state": init_serve_state(cfg, args.batch_slots, args.cache_len),
            "rcache": self.engine.init_cache(args.batch_slots),
        }
        self.all_spans: list[dict[str, Any]] = []
        self._decode_variants: dict[tuple, Any] = {}
        self._decode_jit = self._jit_decode_factory()
        self._control_every = args.control_every
        # repeat traffic: every stream in this replica loops one token (a
        # distinct one per replica), so consecutive decode steps feed
        # near-identical activations — the paper's sticky-session reuse case,
        # and the steady skip baseline the SLO watcher judges collapses
        # against. random traffic exercises the no-reuse extreme instead.
        self.sticky_token = 7 + 4 * seed if args.traffic == "repeat" else None
        self.batcher = self._build_batcher(args)
        rng = np.random.default_rng(seed)
        for i in range(args.requests):
            if self.sticky_token is not None:
                prompt = np.full((args.prompt_len,), self.sticky_token,
                                 dtype=np.int32)
            else:
                prompt = rng.integers(0, cfg.vocab, size=(args.prompt_len,),
                                      dtype=np.int32)
            self.batcher.submit(Request(
                rid=i,
                prompt=prompt,
                max_new_tokens=args.max_new,
                # distinct session mix per replica: replica i cycles 2+i
                # session identities, so admission predictors diverge
                session=f"sess-{i % self._n_sessions}",
            ))

    @property
    def _n_sessions(self) -> int:
        return 2 + int(self.name.lstrip("r") or 0) \
            if self.name.startswith("r") else 2

    # ------------------------------------------------------------ jit plumbing
    def _spec_signature(self) -> tuple:
        return tuple(sorted(self.engine.sites.items()))

    def _jit_decode_factory(self):
        # same variant memoisation + donation as launch/serve.py: compiled
        # executables are keyed by the sites' full spec signature, and the
        # serving state + reuse cache are donated through the step
        key = self._spec_signature()
        fn = self._decode_variants.get(key)
        if fn is None:
            self._decode_variants[key] = fn = jit_decode(self.cfg, self.engine)
        return fn

    # --------------------------------------------------------- batcher wiring
    def _build_batcher(self, args) -> ContinuousBatcher:
        from repro.sensor.aggregate import slot_telemetry

        cfg, params = self.cfg, self.params

        prefill_jit = jit_prefill(cfg)

        def prefill_fn(prompt, slot):
            full = jnp.zeros((args.batch_slots, prompt.shape[1]), jnp.int32)
            full = full.at[slot].set(jnp.asarray(prompt[0]))
            logits, new_state = prefill_jit(
                params, full, self.sstate["state"])
            self.sstate["state"] = new_state
            self.sstate["rcache"] = reset_slot(self.sstate["rcache"], slot)
            return int(greedy_sample(logits[slot: slot + 1, -1:])[0, 0])

        def decode_fn(tokens):
            if self.injector is not None:
                self.injector.maybe_stall(self.batcher.stats["steps"] + 1)
            logits, new_state, new_rcache = self._decode_jit(
                params, jnp.asarray(tokens), self.sstate["state"],
                self.sstate["rcache"])
            self.sstate["state"] = new_state
            self.sstate["rcache"] = new_rcache
            out = np.asarray(greedy_sample(logits[:, -1:]))[:, :, 0] \
                if logits.ndim == 4 else np.asarray(greedy_sample(logits))
            if self.sticky_token is not None:
                # teacher-force the loop token: full decode compute ran (and
                # synced — `out` forced the device round trip), only the
                # emitted token is pinned so the stream keeps repeating
                out = np.full_like(out, self.sticky_token)
            return out

        def telemetry_fn(slot):
            t = slot_telemetry(self.engine, self.sstate["rcache"], slot)
            if self.injector is not None:
                t = self.injector.on_telemetry(
                    t, self.batcher.stats["steps"])
            return t

        def on_retire(req):
            self.predictor.observe_retirement(req)
            self.sstate["rcache"] = reset_slot(
                self.sstate["rcache"], req.slot, admission=self.predictor)

        def on_step(step_idx):
            if self.injector is not None:
                n_fired = len(self.injector.fired)
                self.sstate["rcache"] = self.injector.on_cache_update(
                    self.sstate["rcache"], step_idx)
                if len(self.injector.fired) > n_fired:
                    print(f"[{self.name}] inject @step {step_idx}: "
                          f"{self.injector.fired[-1]['detail']}")
            if step_idx % self._control_every == 0:
                with events.context(window=step_idx):
                    rep = self.controller.step(
                        self.engine, self.sstate["rcache"], step=step_idx)
                    observe_control_report(self.registry, rep)
                    if self.controller.last_guard_report is not None:
                        observe_guard_report(
                            self.registry, self.controller.last_guard_report)
                    # one cumulative sensor snapshot per control window —
                    # the fleet plane's windowed-skip stream
                    self.engine.sensor_report(
                        self.sstate["rcache"]).write_jsonl(self.sensor_path)
                if rep.changed:
                    self._decode_jit = self._jit_decode_factory()

        return ContinuousBatcher(
            batch_slots=args.batch_slots,
            prefill_fn=prefill_fn,
            decode_fn=decode_fn,
            max_steps=args.requests * args.max_new + 8,
            telemetry_fn=telemetry_fn,
            on_retire=on_retire,
            slot_sim_fn=self.predictor.slot_affinity,
            on_step=on_step,
            predict_sim_fn=self.predictor.predict,
            on_place=self.predictor.on_placed,
        )

    # ---------------------------------------------------------------- driving
    def turn(self) -> bool:
        """One interleaved scheduling turn, correlation-scoped to this
        replica; spans close inside the turn, so draining the (module-global)
        buffer here attributes them to the right replica."""
        if not self.batcher.pending:
            return False
        with events.context(run=self.run, replica=self.name):
            alive = self.batcher.step_once()
        drained = obs_trace.drain_spans()
        if drained:
            self.all_spans.extend(drained)
            with open(self.spans_path, "a") as f:
                for row in drained:
                    f.write(json.dumps(row) + "\n")
        return alive

    def finalize(self) -> None:
        """End-of-run emission, stamped with this replica's identity."""
        from repro.obs.export import write_jsonl, write_prometheus

        with events.context(run=self.run, replica=self.name):
            report = self.engine.sensor_report(self.sstate["rcache"])
            report.write_jsonl(self.sensor_path)
            observe_sensor_report(self.registry, report)
            observe_spans(self.registry, self.all_spans)
            write_prometheus(
                os.path.join(self.obs_dir, "metrics.prom"), self.registry)
            write_jsonl(self.metrics_path, self.registry)
        print(f"[{self.name}] run={self.run} "
              f"served={len(self.batcher.completed)} "
              f"steps={self.batcher.stats['steps']} "
              f"trips={self.breaker.total_trips} "
              f"quarantined={self.breaker.quarantined_lanes()}")


def main(argv: list[str] | None = None) -> None:
    from repro.obs.fleet import (
        FleetAggregator,
        export_fleet_metrics,
    )
    from repro.obs.slo import SLOConfig, SLOWatcher
    from repro.obs.stream import ReplicaStream

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6,
                    help="requests submitted PER replica")
    ap.add_argument("--batch-slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--control-every", type=int, default=6,
                    help="control-plane (and sensor-window) cadence in "
                    "decode steps, per replica")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--traffic", choices=("repeat", "random"),
                    default="repeat",
                    help="repeat: sticky streams looping one token per "
                    "replica (steady reuse, the skip baseline SLO collapse "
                    "is judged against); random: uncorrelated tokens "
                    "(the no-reuse extreme)")
    ap.add_argument("--out", required=True,
                    help="fleet dir: replica obs subdirs + fleet artifacts")
    ap.add_argument("--inject", default=None, metavar="SCENARIO[:k=v,...]",
                    help="arm a repro.guard.inject scenario on ONE replica "
                    "(see --inject-replica)")
    ap.add_argument("--inject-replica", type=int, default=None,
                    help="replica index to arm --inject on (default: last)")
    ap.add_argument("--slo-collapse-frac", type=float, default=0.6)
    ap.add_argument("--slo-consecutive", type=int, default=2)
    ap.add_argument("--slo-min-baseline", type=float, default=0.05)
    ap.add_argument("--slo-p95-target", type=float, default=None)
    ap.add_argument("--baseline-windows", type=int, default=3)
    args = ap.parse_args(argv)

    if args.inject_replica is not None and not args.inject:
        ap.error("--inject-replica requires --inject")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    assert cfg.family != "audio", "encoder archs have no decode path"

    print(f"compile cache: {enable_compile_cache()}")
    obs_trace.enable()
    os.makedirs(args.out, exist_ok=True)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    inject_idx = None
    if args.inject:
        inject_idx = (args.replicas - 1 if args.inject_replica is None
                      else args.inject_replica)
        if not 0 <= inject_idx < args.replicas:
            ap.error(f"--inject-replica {inject_idx} out of range "
                     f"for --replicas {args.replicas}")

    replicas: list[Replica] = []
    for i in range(args.replicas):
        injector = None
        if inject_idx == i:
            from repro.guard import FaultInjector

            injector = FaultInjector.from_spec(args.inject)
            print(f"[r{i}] fault injection armed: {injector.scenario} "
                  f"{injector.params}")
        replicas.append(Replica(
            f"r{i}", cfg, params, args, args.out,
            injector=injector, seed=args.seed + i))
    print(f"fleet: {args.replicas} replicas, "
          + ", ".join(f"{r.name}=run:{r.run}" for r in replicas))

    # live fleet plane: tail the obs dirs the replicas are writing, exactly
    # as an out-of-process aggregator would
    fleet_registry = MetricsRegistry()
    agg = FleetAggregator(
        [ReplicaStream(r.obs_dir, replica=r.name) for r in replicas],
        baseline_windows=args.baseline_windows)
    watcher = SLOWatcher(
        agg,
        SLOConfig(
            collapse_frac=args.slo_collapse_frac,
            collapse_consecutive=args.slo_consecutive,
            min_baseline_skip=args.slo_min_baseline,
            p95_target_s=args.slo_p95_target,
        ),
        registry=fleet_registry,
        alerts_path=os.path.join(args.out, "alerts.jsonl"),
    )

    t0 = obs_trace.now()
    max_turns = args.requests * args.max_new + 16
    for turn in range(max_turns):
        alive = False
        for rep in replicas:
            alive = rep.turn() or alive
        if turn % args.control_every == 0 or not alive:
            agg.poll()
            for alert in watcher.evaluate():
                print(f"SLO alert: {alert['alert_kind']} "
                      f"replica={alert['replica']} site={alert['site'] or '-'}"
                      f" {alert['detail']}")
        if not alive:
            break
    dt = obs_trace.now() - t0

    for rep in replicas:
        rep.finalize()

    # final drain: pick up the end-of-run sensor/metrics rows just written
    agg.poll(final=True)
    for alert in watcher.evaluate():
        print(f"SLO alert: {alert['alert_kind']} replica={alert['replica']} "
              f"site={alert['site'] or '-'} {alert['detail']}")
    export_fleet_metrics(fleet_registry, agg)

    from repro.obs.export import write_prometheus

    report = agg.fleet_report()
    report_path = os.path.join(args.out, "fleet_report.json")
    with open(report_path, "w") as f:
        json.dump(report, f, indent=2)
    n_prom = write_prometheus(
        os.path.join(args.out, "fleet.prom"), fleet_registry)
    print("\n".join(agg.summary_lines()))
    print(f"fleet artifacts -> {args.out} (fleet_report.json, alerts.jsonl "
          f"{len(watcher.alerts)} alerts, fleet.prom {n_prom} lines) "
          f"in {dt:.2f}s")


if __name__ == "__main__":
    main()
