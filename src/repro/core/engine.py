"""ReuseEngine — site registry + dispatch (the CRS instruction analogue).

The paper's flow: the framework prepares a parameter structure (addresses,
lengths, kernelMode, dataflow) and issues `crs` per layer/tile; ReuseSensor
generates the kernel — and parametrizes kernelMode LAYER BY LAYER. Here:

* `register(...)` declares a reuse site (one per unique linear op; sites used
  inside scan-over-layers carry a leading layer dimension in their cache);
* `init_cache(batch)` builds the cache pytree threaded through serve_step —
  including, per site, the ARRAY-RESIDENT control block (`ctrl`): per-layer
  kernelMode ids, live sim_threshold / min_work operating point, per-layer
  flip cooldown and budget-occupancy EMA;
* `apply(...)` executes one site — the crs call; kernelMode is read from the
  ctrl lane the scan sliced for this layer (lax.cond in reuse_linear), so a
  deep stack runs mixed modes inside ONE trace;
* `refresh_modes(cache)` is the host-side policy pass between steps: a
  vectorized per-layer decide over each site's ctrl block. Mode flips are
  array writes (no retrace); only spec-level changes — exec_path / block_k /
  max_active_k — require rebuilding the jitted step, and only those are
  returned.

The engine itself is static configuration; ALL mutable control state lives in
the cache pytree next to the counters, so steps stay pure and jit/pjit-
friendly and the policy's current operating point checkpoints/donates/shards
with the rest of the serving state.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.policy import (
    MODE_BASIC,
    MODE_REUSE,
    ReusePolicy,
    SiteTunables,
    layer_key,
    mode_name,
)
from repro.core.reuse_cache import ReuseSiteSpec, init_site_cache
from repro.core.reuse_linear import ReuseStats, layer_weight, reuse_linear
from repro.sensor.counters import ShardCtx


def clamp_budget(max_active_k: int | None, gk: int) -> int:
    """kernels.ops.clamp_budget, imported lazily: kernels.ops imports
    repro.core.delta, so a module-level import back into the engine closes
    an import cycle for any consumer that loads repro.kernels first."""
    from repro.kernels.ops import clamp_budget as _clamp

    return _clamp(max_active_k, gk)


def _combine_shard_sentinels(
    lanes: dict[str, jax.Array], count: int
) -> dict[str, jax.Array]:
    """Collapse vmapped sentinel lanes [S, L] → [L], preserving each lane's
    detection semantics: disjoint counts SUM (prev_out columns and the
    counter ownership partition split across shards), replicated health
    flags MAX (a single corrupt shard must still trip), and the ctrl range
    bitmask ORs (max would drop bits when different shards fail different
    range checks)."""
    out: dict[str, jax.Array] = {
        "bad_out": jnp.sum(lanes["bad_out"], axis=0),
        "bad_sim": jnp.max(lanes["bad_sim"], axis=0),
        "steps_l": lanes["steps_l"][0],
    }
    if "ctrl_bad" in lanes:
        out["ctrl_bad"] = functools.reduce(
            jnp.bitwise_or, [lanes["ctrl_bad"][i] for i in range(count)]
        )
        out["quarantine"] = jnp.max(lanes["quarantine"], axis=0)
    if "skipped_l" in lanes:
        out["skipped_l"] = jnp.sum(lanes["skipped_l"], axis=0)
        out["computed_l"] = jnp.sum(lanes["computed_l"], axis=0)
    return out


@functools.partial(jax.jit, static_argnames=("shard_axes",))
def _ctrl_snapshot_device(
    cache: dict[str, Any],
    shard_axes: tuple[tuple[str, int, int], ...] = (),
) -> dict[str, Any]:
    """ONE traced pass over the whole cache pytree gathering everything the
    host-side policy pass reads: per-layer sim_ema means, the ctrl lanes, and
    the sensor tile sums. Before this existed, refresh_modes/refresh_exec_
    paths issued ~7 device→host syncs PER SITE per control interval; now the
    reductions run in one compiled executable and the host pulls one tiny
    pytree (see ReuseEngine.ctrl_snapshot).

    The guard plane's array sentinels (non-finite flags, ctrl-lane range
    bitmasks, per-layer counter lanes — repro.guard.sentinel) ride the same
    traced pass, so fault DETECTION costs zero extra device→host syncs.

    `shard_axes` (static) lists the model-sharded sites as
    (name, shard_axis, n_shards). For those entries the snapshot is ALSO the
    once-per-control-window cross-mesh sensor reduce: the sums below run over
    the shard axis of mesh-placed counter arrays, so SPMD partitioning lowers
    them to the one all-reduce per window the design allows (no hot-path
    collectives), and the host still pulls one tiny replicated pytree.
    Replicated ctrl/sim lanes collapse to shard lane 0; per-shard skip lanes
    (`skipped_shard`/`computed_shard`, [S]) ride along for the controller's
    per-shard journal entries at zero extra transfers."""
    from repro.guard.sentinel import sentinel_lanes

    shard_of = {name: (ax, count) for name, ax, count in shard_axes}
    snap: dict[str, Any] = {}
    for name, entry in cache.items():
        s: dict[str, jax.Array] = {}
        sh = shard_of.get(name)
        ctrl = entry.get("ctrl")
        if ctrl is not None:
            sim = entry["sim_ema"]
            sim_l = sim if sim.ndim == 0 else jnp.mean(sim, axis=-1)
            if sh is not None:  # replicated across shards → lane 0
                ax = sh[0]
                sim_l = jnp.take(sim_l, 0, axis=ax)
                s["sim_l"] = jnp.atleast_1d(sim_l).astype(jnp.float32)
                s["mode_id"] = jnp.atleast_1d(
                    jnp.take(ctrl["mode_id"], 0, axis=ax))
                s["sim_threshold"] = jnp.atleast_1d(
                    jnp.take(ctrl["sim_threshold"], 0, axis=ax))
                s["min_work"] = jnp.atleast_1d(
                    jnp.take(ctrl["min_work"], 0, axis=ax))
                s["cooldown"] = jnp.atleast_1d(
                    jnp.take(ctrl["cooldown"], 0, axis=ax))
            else:
                s["sim_l"] = jnp.atleast_1d(sim_l).astype(jnp.float32)
                s["mode_id"] = jnp.atleast_1d(ctrl["mode_id"])
                s["sim_threshold"] = jnp.atleast_1d(ctrl["sim_threshold"])
                s["min_work"] = jnp.atleast_1d(ctrl["min_work"])
                s["cooldown"] = jnp.atleast_1d(ctrl["cooldown"])
        sensor = entry.get("sensor")
        if sensor is not None:
            # ownership partition ⇒ the plain sum over ALL axes (layers AND
            # shards) IS the global count — this is the mesh reduce.
            s["skipped"] = jnp.sum(sensor["skipped_tiles"])
            s["computed"] = jnp.sum(sensor["computed_tiles"])
            if sh is not None:
                ax = sh[0]
                lane_axes = tuple(
                    i for i in range(sensor["skipped_tiles"].ndim) if i != ax)
                s["skipped_shard"] = jnp.sum(
                    sensor["skipped_tiles"], axis=lane_axes)
                s["computed_shard"] = jnp.sum(
                    sensor["computed_tiles"], axis=lane_axes)
        if ctrl is not None:
            if sh is None:
                s.update(sentinel_lanes(entry))
            else:
                ax, count = sh
                lanes = jax.vmap(sentinel_lanes, in_axes=ax)(entry)
                s.update(_combine_shard_sentinels(lanes, count))
        snap[name] = s
    return snap


@dataclasses.dataclass
class ReuseEngine:
    policy: ReusePolicy = dataclasses.field(default_factory=ReusePolicy)
    impl: str = "jnp"
    sites: dict[str, ReuseSiteSpec] = dataclasses.field(default_factory=dict)
    # per-site leading layer count (0 = unstacked site)
    stacking: dict[str, int] = dataclasses.field(default_factory=dict)
    # exec-path flip cooldown per site: refresh passes left before the next
    # substrate change is allowed (each one retraces the step). kernelMode
    # cooldown is PER LAYER and lives in the cache ctrl block instead.
    exec_cooldown: dict[str, int] = dataclasses.field(default_factory=dict)
    # per-layer mode flips applied by the most recent refresh_modes pass
    # ({site, layer, before, after, sim_ema}; layer None = unstacked) — the
    # controller journals these; they do NOT require a retrace
    last_mode_events: list[dict] = dataclasses.field(default_factory=list)
    # model-axis shard count per site (empty = unsharded engine). Set by
    # shard_sites() BEFORE init_cache; sharded entries carry the shard axis
    # inside the layer axis ([S, ...] unstacked, [L, S, ...] stacked).
    shards: dict[str, int] = dataclasses.field(default_factory=dict)
    # the device mesh a sharded engine runs on (set by shard_sites). With a
    # mesh, each device evaluates only its own shard lane inside a
    # shard_map over the "model" axis — the form a compiled Pallas kernel
    # needs, since GSPMD cannot partition a custom call. Without one the
    # shard lanes are vmapped on whatever devices hold them.
    mesh: Any = None
    # interconnect accounting (bytes, cumulative): the per-window cross-mesh
    # counter reduce riding the ctrl snapshot, and sharded ctrl-lane write
    # fan-out. sensor.cost_model prices these into E_ICI energy.
    ici_reduce_bytes: float = 0.0
    ici_write_bytes: float = 0.0
    # the most recent ctrl_snapshot (host pytree) — the controller reads the
    # per-shard skip lanes from here instead of paying a second device_get
    last_snapshot: dict[str, Any] | None = None

    def register(
        self,
        name: str,
        in_features: int,
        out_features: int,
        *,
        n_layers: int = 0,
        block_m: int = 8,
        block_k: int = 256,
        block_n: int = 128,
        mode: str = "auto",
    ) -> ReuseSiteSpec:
        dataflow = self.policy.decide_dataflow(in_features, out_features)
        # The policy's per-site table overrides the caller's tile granularity;
        # the resolved block_k lands in the spec and from there reaches the
        # Pallas kernel dispatch (reuse_linear → ops.reuse_matmul). The same
        # resolution carries the execution substrate: a tuned exec_path /
        # max_active_k selects the compacted tier right at registration.
        block_k = self.policy.resolve_block_k(name, block_k)
        spec = ReuseSiteSpec(
            name=name,
            in_features=in_features,
            out_features=out_features,
            block_m=block_m,
            block_k=block_k,
            block_n=block_n,
            mode=mode,
            dataflow=dataflow,
            exec_path=self.policy.resolve_exec_path(name),
            max_active_k=self.policy.resolve_max_active_k(name),
        )
        self.sites[name] = spec
        self.stacking[name] = n_layers
        self.exec_cooldown[name] = 0
        return spec

    def shard_sites(self, n_shards: int, mesh=None) -> dict[str, int]:
        """Plan an N-way model-axis split of every registered site — the
        sharded-serving entry point, called BEFORE init_cache. Validates
        divisibility up front (a clear error beats a reshape failure deep in
        the traced step) and records the plan in `self.shards`; init_cache
        then expands every entry with the shard axis, apply() dispatches
        through the per-shard path, and the ctrl snapshot collapses shard
        lanes back out. `mesh` (whose "model" axis is n_shards wide) makes
        apply() run each shard on its own device. n_shards <= 1 clears the
        plan (unsharded)."""
        from repro.dist.shard import validate_shardable

        self.mesh = None
        if n_shards <= 1:
            self.shards = {}
            return self.shards
        for spec in self.sites.values():
            validate_shardable(spec, n_shards)
        if mesh is not None and int(mesh.shape["model"]) != n_shards:
            raise ValueError(
                f"mesh model axis is {mesh.shape['model']} wide, not "
                f"{n_shards}")
        self.shards = {name: n_shards for name in self.sites}
        self.mesh = mesh
        return self.shards

    def init_cache(self, batch: int) -> dict[str, Any]:
        cache: dict[str, Any] = {}
        for name, spec in self.sites.items():
            n_shards = self.shards.get(name, 0)
            if n_shards:
                from repro.dist.shard import plan_local_spec

                spec = plan_local_spec(spec, n_shards)
            entry = init_site_cache(spec, batch, self.policy.resolve(name))
            if n_shards:
                # shard axis first (innermost), layer axis broadcast below
                # wraps it: [S, ...] unstacked → [L, S, ...] stacked. Initial
                # state is identical across shards (prev_out is zeros at the
                # local N), so a broadcast IS the sharded init.
                entry = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x, (n_shards, *x.shape)).copy(),
                    entry,
                )
            n_layers = self.stacking[name]
            if n_layers:
                entry = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (n_layers, *x.shape)).copy(),
                    entry,
                )
                # per-layer tunables rows ("site@layer") land in the ctrl
                # lanes here; spec-level knobs stay site-granular
                ts = [self.policy.resolve(name, layer=layer)
                      for layer in range(n_layers)]
                thr = jnp.asarray([t.sim_threshold for t in ts], jnp.float32)
                mw = jnp.asarray([t.min_work_flops for t in ts], jnp.float32)
                if n_shards:  # per-layer lanes replicate across shards
                    thr = jnp.broadcast_to(
                        thr[:, None], (n_layers, n_shards))
                    mw = jnp.broadcast_to(mw[:, None], (n_layers, n_shards))
                entry["ctrl"] = dict(
                    entry["ctrl"], sim_threshold=thr, min_work=mw,
                )
            cache[name] = entry
        return cache

    def apply(
        self,
        name: str,
        x: jax.Array,
        w: jax.Array,
        b: jax.Array | None,
        cache_entry: dict[str, jax.Array],
        layer: jax.Array | None = None,
    ) -> tuple[jax.Array, dict[str, jax.Array], ReuseStats]:
        """One site call. `w` is `[K, N]`, or the layer stack `[L, K, N]`
        with the scan's `layer` index (see reuse_linear)."""
        spec = self.sites[name]
        # Explicitly pinned sites keep the static single-branch dispatch;
        # "auto" sites branch on the ctrl lane the caller's scan sliced.
        mode = spec.mode if spec.mode in ("reuse", "basic") else None
        # named_scope labels the site in device traces/HLO, so a profiler
        # window (serve --profile-dir) attributes device time per reuse site.
        with jax.named_scope(f"reuse_site:{name}"):
            if self.shards.get(name):
                return self._apply_sharded(
                    name, x, layer_weight(w, layer), b, cache_entry, mode)
            return reuse_linear(
                x, w, b, cache_entry, spec, mode=mode, impl=self.impl,
                layer=layer,
            )

    def _apply_sharded(
        self,
        name: str,
        x: jax.Array,
        w: jax.Array,
        b: jax.Array | None,
        entry: dict[str, jax.Array],
        mode: str | None,
    ) -> tuple[jax.Array, dict[str, jax.Array], ReuseStats]:
        """One sharded site call: vmap the shard-local evaluation over the
        entry's shard axis. The weight panel splits column-wise to match
        (`w[:, s·nl:(s+1)·nl]` per shard); x is replicated; every cache leaf
        carries the shard axis uniformly, so `in_axes=0` maps the whole
        entry. NOTHING here crosses shards — no gather, no reduce — which is
        the hot-path invariant the HLO check pins. On an engine with a mesh
        the vmap runs inside a shard_map, so each device maps only the shard
        lane it holds.

        kernelMode dispatch lifts OUTSIDE the vmap: `lax.cond` under vmap
        lowers to a select that executes BOTH branches on every shard, so the
        branch is taken once on the (replicated) layer ctrl lane and each arm
        vmaps a statically-moded evaluation."""
        spec = self.sites[name]
        n_shards = self.shards[name]
        nl = spec.out_features // n_shards
        k = w.shape[0]
        lead = x.shape[:-1]
        local = dataclasses.replace(spec, out_features=nl)
        gn_total = -(-spec.out_features // spec.block_n)
        ws = jnp.moveaxis(w.reshape(k, n_shards, nl), 1, 0)   # [S, K, nl]
        bs = None if b is None else b.reshape(n_shards, nl)
        idx = jnp.arange(n_shards, dtype=jnp.int32)
        if mode is None and entry.get("ctrl") is None:
            raise ValueError(
                f"site {name!r}: sharded mode=None needs a ctrl block "
                "in the cache entry (engine.init_cache creates it)"
            )

        def eval_lanes(x, idx, ws, bs, entry):
            def _sharded_eval(static_mode: str):
                def one(i, wl, bl, el):
                    shard = ShardCtx(index=i, count=n_shards,
                                     n_total=spec.out_features,
                                     gn_total=gn_total)
                    return reuse_linear(
                        x, wl, bl, el, local, mode=static_mode,
                        impl=self.impl, shard=shard,
                    )

                axes = (0, 0, None if b is None else 0, 0)
                return lambda: jax.vmap(one, in_axes=axes)(idx, ws, bs, entry)

            if mode is not None:
                return _sharded_eval(mode)()
            # the layer's mode lane, replicated across shards → lane 0
            pred = jnp.reshape(entry["ctrl"]["mode_id"], (-1,))[0] > 0
            return jax.lax.cond(
                pred, _sharded_eval("reuse"), _sharded_eval("basic")
            )

        if self.mesh is not None:
            lanes = PartitionSpec("model")
            eval_lanes = jax.shard_map(
                eval_lanes, mesh=self.mesh,
                in_specs=(PartitionSpec(), lanes, lanes, lanes, lanes),
                out_specs=lanes, check_vma=False,
            )
        out_s, new_entry, stats_s = eval_lanes(x, idx, ws, bs, entry)
        # [S, *lead, nl] → [*lead, S, nl] → [*lead, N]
        out = jnp.moveaxis(out_s, 0, -2).reshape(*lead, spec.out_features)
        if self.mesh is not None:
            # gather the activation here, so the rest of the layer runs
            # replicated exactly as on one device instead of inheriting the
            # model-axis split (which would reorder its reductions)
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(self.mesh, PartitionSpec()))
        stats = jax.tree.map(lambda a: a[0], stats_s)  # replicated per shard
        return out, new_entry, stats

    # ------------------------------------------------ ctrl-block interrogation

    @staticmethod
    def entry_mode_ids(entry: dict[str, Any]) -> np.ndarray:
        """A site's per-layer mode ids as a 1-d host array ([1] unstacked)."""
        return np.atleast_1d(np.asarray(entry["ctrl"]["mode_id"]))

    def _mode_ids(self, cache: dict[str, Any], name: str) -> np.ndarray:
        """Per-layer mode ids with the shard lane collapsed (mode lanes are
        replicated across model shards, so lane 0 is the site truth)."""
        ids = np.asarray(cache[name]["ctrl"]["mode_id"])
        if self.shards.get(name, 0):
            from repro.dist.shard import shard_axis_of

            ids = np.take(ids, 0, axis=shard_axis_of(
                self.stacking.get(name, 0)))
        return np.atleast_1d(ids)

    def layer_modes(self, cache: dict[str, Any], name: str) -> list[str]:
        return [mode_name(m) for m in self._mode_ids(cache, name)]

    def site_mode(self, cache: dict[str, Any], name: str) -> str:
        """One site's kernelMode summary: "reuse"/"basic" when uniform over
        layers, "mixed" when a stack settled distinct per-layer modes."""
        ids = self._mode_ids(cache, name)
        if np.all(ids == ids[0]):
            return mode_name(ids[0])
        return "mixed"

    def mode_summary(self, cache: dict[str, Any]) -> dict[str, str]:
        return {name: self.site_mode(cache, name) for name in self.sites}

    def set_mode(
        self, cache: dict[str, Any], name: str, mode: str,
        *, layer: int | None = None,
    ) -> None:
        """Force kernelMode for a site (all layers, or one layer's lane) by
        writing the ctrl block — an array write, no retrace."""
        mid = MODE_REUSE if mode == "reuse" else MODE_BASIC
        entry = cache[name]
        cur = entry["ctrl"]["mode_id"]
        new = jnp.full_like(cur, mid) if layer is None else cur.at[layer].set(mid)
        cache[name] = dict(entry, ctrl=dict(entry["ctrl"], mode_id=new))

    # ------------------------------------------------------- live write paths

    def apply_tunables(
        self,
        name: str,
        t: SiteTunables,
        cache: dict[str, Any] | None = None,
        *,
        layer: int | None = None,
    ) -> bool:
        """Install live tunables — the online retuner's write path.

        `layer=None` replaces the site-level policy-table entry; spec fields
        baked into the traced dispatch re-resolve here: block_k, and — for a
        site already ON a compacted path — its k-extent budget. `layer=i`
        installs a per-layer row (`"site@i"` key) instead and touches NO spec
        field (per-layer knobs are array-resident by construction).

        With `cache` given, the affected ctrl lanes (sim_threshold/min_work)
        are re-synced from the updated table in the same pass, so the next
        refresh decides on the new operating point without a separate sync.
        Mode and exec-path *transitions* stay with `refresh_modes`, which
        carries the hysteresis margin and the flip cooldowns. Returns True
        when the SPEC changed, so callers rebuild the jitted step."""
        if layer is not None:
            self.policy.site_tunables[layer_key(name, layer)] = t
            self._sync_ctrl(name, cache)
            return False
        self.policy.site_tunables[name] = t
        spec = self.sites[name]
        new = spec
        if t.block_k is not None and int(t.block_k) != spec.block_k:
            new = dataclasses.replace(new, block_k=int(t.block_k))
            if new.exec_path in ("ragged", "compact") and new.max_active_k:
                # the budget's unit is K-blocks OF block_k: rescale it so the
                # covered K extent survives the granularity change (else a
                # halved block_k silently halves the budgeted extent and
                # every evaluation overflows into the full-extent fallback).
                # The table entry syncs to the rescaled value too, so the
                # next retune interval can't re-install the old-unit number.
                gk = -(-new.in_features // new.block_k)
                scaled = round(new.max_active_k * spec.block_k / new.block_k)
                new = dataclasses.replace(
                    new, max_active_k=clamp_budget(int(scaled), gk)
                )
                self.policy.site_tunables[name] = dataclasses.replace(
                    t, max_active_k=new.max_active_k
                )
        if (
            t.max_active_k is not None
            and new.exec_path in ("ragged", "compact")
            and spec.block_k == new.block_k  # rescale wins on a block_k move
            and int(t.max_active_k) != new.max_active_k
        ):
            gk = -(-new.in_features // new.block_k)
            new = dataclasses.replace(
                new, max_active_k=clamp_budget(int(t.max_active_k), gk)
            )
        self._sync_ctrl(name, cache)
        if new == spec:
            return False
        self.sites[name] = new
        return True

    def _sync_ctrl(self, name: str, cache: dict[str, Any] | None) -> None:
        """Re-derive a site's ctrl sim_threshold/min_work lanes from the
        policy table (per-layer rows win over the site row, as in resolve)."""
        if cache is None:
            return
        entry = cache.get(name)
        if entry is None or "ctrl" not in entry:
            return
        n_layers = self.stacking.get(name, 0)
        if n_layers:
            ts = [self.policy.resolve(name, layer=layer)
                  for layer in range(n_layers)]
            thr = jnp.asarray([t.sim_threshold for t in ts], jnp.float32)
            mw = jnp.asarray([t.min_work_flops for t in ts], jnp.float32)
        else:
            t = self.policy.resolve(name)
            thr = jnp.asarray(t.sim_threshold, jnp.float32)
            mw = jnp.asarray(t.min_work_flops, jnp.float32)
        n_shards = self.shards.get(name, 0)
        if n_shards:  # replicate tunable lanes across the shard axis
            if n_layers:
                thr = jnp.broadcast_to(thr[:, None], (n_layers, n_shards))
                mw = jnp.broadcast_to(mw[:, None], (n_layers, n_shards))
            else:
                thr = jnp.broadcast_to(thr, (n_shards,))
                mw = jnp.broadcast_to(mw, (n_shards,))
            self.ici_write_bytes += float(thr.size + mw.size) * 4
        cache[name] = dict(
            entry, ctrl=dict(entry["ctrl"], sim_threshold=thr, min_work=mw)
        )

    def set_budget(self, name: str, budget: int) -> bool:
        """Re-point a compacted site's static k-extent budget — the online
        budget adapter's write path. The budget is a grid extent baked into
        the traced kernel, so it stays site-granular (per-layer occupancy is
        the MEASUREMENT — ctrl["occupancy"] / the per-layer overflow counters
        — feeding this one knob). Keeps the policy table in sync so the next
        exec-path refresh or retune doesn't silently revert the adaptation.
        Returns True when the spec changed (retrace)."""
        spec = self.sites[name]
        if spec.exec_path not in ("ragged", "compact"):
            return False
        gk = -(-spec.in_features // spec.block_k)
        budget = clamp_budget(int(budget), gk)
        if budget == spec.max_active_k:
            return False
        self.sites[name] = dataclasses.replace(spec, max_active_k=budget)
        self.policy.site_tunables[name] = dataclasses.replace(
            self.policy.resolve(name), max_active_k=budget
        )
        return True

    # -------------------------------------------------- host-side policy pass

    def _shard_axes_static(self) -> tuple[tuple[str, int, int], ...]:
        """Hashable shard layout for the jitted snapshot's static arg."""
        from repro.dist.shard import shard_axis_of

        return tuple(sorted(
            (name, shard_axis_of(self.stacking.get(name, 0)), count)
            for name, count in self.shards.items()
        ))

    def ctrl_snapshot(self, cache: dict[str, Any]) -> dict[str, Any]:
        """Pull the policy pass's inputs for ALL sites in one device round
        trip: the traced `_ctrl_snapshot_device` reduces on device, a single
        `jax.device_get` materializes the result as host numpy.

        On a sharded engine this snapshot IS the once-per-window cross-mesh
        sensor reduce; the payload it moves is metered into
        `ici_reduce_bytes` so the cost model can price it as E_ICI."""
        snap_dev = _ctrl_snapshot_device(
            cache, shard_axes=self._shard_axes_static())
        if self.shards:
            self.ici_reduce_bytes += float(sum(
                leaf.size * leaf.dtype.itemsize
                for name in self.shards
                for leaf in jax.tree.leaves(snap_dev.get(name, {}))
            ))
        snap = jax.device_get(snap_dev)
        self.last_snapshot = snap
        return snap

    def refresh_modes(self, cache: dict[str, Any]) -> dict[str, str]:
        """Host-side policy pass: one BATCHED per-layer decide per site.

        Reads each site's per-layer sim_ema means and its ctrl block
        (mode_id / sim_threshold / min_work / cooldown arrays), re-decides
        kernelMode lane-wise (hysteretically: the signal must leave the
        current mode's band by the layer's margin, and a freshly-flipped lane
        is frozen for its `hysteresis_steps` passes), and writes the new
        mode_id/cooldown arrays back into the cache — an array write, NOT a
        retrace, so distinct layers of one scanned stack settle distinct
        modes at zero recompile cost. A pass where any lane's wanted flip was
        cooldown-vetoed bumps the site's `suppressed_flips` counter once.
        Applied per-layer flips land in `self.last_mode_events` for the
        controller's journal.

        The same pass re-decides each site's execution substrate
        (`exec_path`) from its measured tile-skip rate. Exec flips ARE spec
        changes (the grid geometry is traced), so only they are returned:
        {site: "exec:<path>"} — callers rebuild the jitted step exactly when
        this dict is non-empty."""
        self.last_mode_events = []
        snap = self.ctrl_snapshot(cache)
        for name, spec in self.sites.items():
            entry = cache[name]
            ctrl = entry.get("ctrl")
            if ctrl is None:
                continue
            s = snap[name]
            # [L, M] stacked / [M] unstacked / scalar legacy → per-layer [L]
            sim_l = np.asarray(s["sim_l"], np.float64)
            mode_id = np.asarray(s["mode_id"])
            n_lanes = mode_id.shape[0]
            if sim_l.shape[0] != n_lanes:
                sim_l = np.broadcast_to(sim_l, (n_lanes,))
            thr = np.asarray(s["sim_threshold"], np.float64)
            mw = np.asarray(s["min_work"], np.float64)
            cd = np.asarray(s["cooldown"], np.int64)
            stacked = self.stacking.get(name, 0) > 0
            ts = [
                self.policy.resolve(name, layer=layer if stacked else None)
                for layer in range(n_lanes)
            ]
            margin = np.asarray([t.hysteresis_margin for t in ts])
            hyst = np.asarray([t.hysteresis_steps for t in ts])
            quar = s.get("quarantine")
            want = self.policy.decide_modes(
                spec, sim_l, mode_id, thr, mw, hysteresis_margin=margin,
                quarantine=None if quar is None else np.asarray(quar),
            )
            flip = want != mode_id
            vetoed = flip & (cd > 0)
            applied = flip & ~vetoed
            new_mode = np.where(applied, want, mode_id)
            new_cd = np.where(applied, hyst, np.maximum(cd - 1, 0))
            if vetoed.any() and "sensor" in entry:
                sensor = dict(entry["sensor"])
                sensor["suppressed_flips"] = sensor["suppressed_flips"] + 1
                entry = dict(entry, sensor=sensor)
            for lane in np.nonzero(applied)[0]:
                self.last_mode_events.append({
                    "site": name,
                    "layer": int(lane) if stacked else None,
                    "before": mode_name(mode_id[lane]),
                    "after": mode_name(new_mode[lane]),
                    "sim_ema": float(sim_l[lane]),
                })
            if applied.any():
                # any-flip-freezes-the-site: a mode flip also holds the
                # site's exec substrate still for the cooldown (the exec
                # loop reciprocates by freezing mode lanes) — churn in one
                # control dimension must not compound with the other
                self.exec_cooldown[name] = max(
                    self.exec_cooldown.get(name, 0),
                    int(hyst[applied].max()),
                )
            shape = jnp.shape(ctrl["mode_id"])
            if name in self.shards:
                # decided lanes are per-layer [L]; the ctrl block is
                # [L, S] / [S] — replicate the decision across shards
                # (every shard runs the same layer mode) and meter the
                # sharded write fan-out for the E_ICI rollup
                stacked_w = self.stacking.get(name, 0) > 0
                new_mode_w = np.broadcast_to(
                    new_mode[:, None] if stacked_w else new_mode, shape)
                new_cd_w = np.broadcast_to(
                    new_cd[:, None] if stacked_w else new_cd, shape)
                self.ici_write_bytes += float(np.prod(shape)) * (1 + 4)
            else:
                new_mode_w = new_mode.reshape(shape)
                new_cd_w = new_cd.reshape(shape)
            entry = dict(entry, ctrl=dict(
                ctrl,
                mode_id=jnp.asarray(new_mode_w, jnp.int8),
                cooldown=jnp.asarray(new_cd_w, jnp.int32),
            ))
            cache[name] = entry
        return self.refresh_exec_paths(cache, snapshot=snap)

    def refresh_exec_paths(
        self, cache: dict[str, Any], *, snapshot: dict[str, Any] | None = None,
    ) -> dict[str, str]:
        """Promote/demote execution substrates from MEASURED skip rates.

        Cumulative tile counters smooth the signal; exec flips carry their
        own site-level cooldown (each one retraces the step — unlike mode
        flips, which are ctrl-array writes); a site with no measured reuse
        evaluations keeps its current path. Caveat: after a live block_k
        change (apply_tunables) the cumulative rate mixes tile units across
        granularities and converges to the new regime only asymptotically —
        the online controller therefore drives promotion through solver
        pins computed from clean windowed deltas, and this pass is the
        fallback for unpinned sites. Returns {site: "exec:<path>"} for
        sites that moved."""
        from repro.core.reuse_cache import resolve_exec_path

        if snapshot is None:
            snapshot = self.ctrl_snapshot(cache)
        changed: dict[str, str] = {}
        for name, spec in self.sites.items():
            s = snapshot.get(name, {})
            if "skipped" not in s:
                continue
            skipped = float(s["skipped"])
            computed = float(s["computed"])
            total = skipped + computed
            if total <= 0:
                continue
            new_path = self.policy.decide_exec_path(
                spec, skipped / total, impl=self.impl
            )
            if new_path == resolve_exec_path(spec, self.impl):
                self.exec_cooldown[name] = max(
                    0, self.exec_cooldown.get(name, 0) - 1)
                continue
            if self.exec_cooldown.get(name, 0) > 0:
                self.exec_cooldown[name] -= 1
                continue
            gk = -(-spec.in_features // spec.block_k)
            budget = None
            if new_path in ("ragged", "compact"):
                budget = self.policy.resolve_max_active_k(name)
                if budget is None:
                    budget = self.policy.ragged_budget(gk, skipped / total)
            self.sites[name] = dataclasses.replace(
                spec, exec_path=new_path, max_active_k=budget
            )
            changed[name] = f"exec:{new_path}"
            hyst = self.policy.resolve(name).hysteresis_steps
            self.exec_cooldown[name] = hyst
            # the reciprocal freeze: an exec flip (a retrace) also holds the
            # site's mode lanes still for the cooldown
            entry = cache[name]
            if "ctrl" in entry:
                ctrl = entry["ctrl"]
                cache[name] = dict(entry, ctrl=dict(
                    ctrl,
                    cooldown=jnp.maximum(
                        ctrl["cooldown"], jnp.int32(hyst)),
                ))
        return changed

    def sensor_report(self, cache: dict[str, Any]):
        """Measured reuse accounting for the whole model — the ReuseSensor's
        bypassed-computation / skipped-weight-load counts, reduced host-side
        from the counters the kernels updated.

        Returns a repro.sensor.aggregate.SensorReport (per-site, per-layer,
        whole-model, JSONL-emittable)."""
        from repro.sensor.aggregate import build_report

        return build_report(self, cache)
