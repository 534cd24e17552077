"""Model-axis sharding of the reuse cache — plan, placement, HLO evidence.

The sharding rule is the Proximu$ one (PAPERS.md): reuse state lives WITH the
weights it shadows. A weight-stationary linear site [K, N] splits N-ways on
the mesh "model" axis, so shard s owns the weight columns `[s·N/S, (s+1)·N/S)`
and, with them, the only cache leaf that is N-shaped: `prev_out`. Everything
M/K-shaped — `prev_q`, `scale`, `sim_ema`, `steps`, the ctrl lanes, the
sensor counters — is replicated per shard (the quantize→delta→mask compare
path needs the full K row and therefore runs identically on every shard:
shard-LOCAL, zero collectives). The shard axis sits INSIDE the layer axis:
unstacked entries carry leading [S, ...], stacked entries [L, S, ...], so
`lax.scan` over layers still slices its leading axis and the layer body sees
a clean [S, ...] shard block for `vmap`.

Counter accounting under replication is the ownership partition documented in
`repro.sensor.counters`: per-shard counter lanes are DISJOINT slices of the
dense-baseline accounting, so their plain sum reproduces the unsharded
counters bitwise — the invariant the shard-parity tests pin.

This module carries the pieces that are about *placement*, not execution:
local-spec planning with divisibility validation, `NamedSharding` assignment
for a sharded cache pytree, and the cache-buffer shape signatures the HLO
no-gather assertion (`roofline.hlo_parse.cache_collective_violations`)
matches collective operands against.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.reuse_cache import ReuseSiteSpec
from repro.sensor.counters import (  # noqa: F401  (re-exported: one import site)
    COUNTER_SHARD_REDUCE,
    ShardCtx,
    owned_k_mask,
    owned_panel_count,
)


def validate_shardable(spec: ReuseSiteSpec, n_shards: int) -> None:
    """Raise with an actionable message when a site can't split N-ways."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if spec.out_features % n_shards:
        raise ValueError(
            f"site {spec.name!r}: out_features={spec.out_features} is not "
            f"divisible by {n_shards} model shards — pick a mesh whose model "
            f"axis divides every reuse site's N"
        )


def plan_local_spec(spec: ReuseSiteSpec, n_shards: int) -> ReuseSiteSpec:
    """The shard-local site spec: same site, N/S output columns.

    Only `out_features` changes — block geometry, dataflow, exec_path and the
    k-extent budget are N-independent (K is never split), so the shard-local
    evaluation is the same traced program at a narrower weight panel.
    """
    validate_shardable(spec, n_shards)
    return dataclasses.replace(
        spec, out_features=spec.out_features // n_shards
    )


def shard_axis_of(n_layers: int) -> int:
    """Position of the shard axis in a site's cache leaves: inside the layer
    axis ([L, S, ...] stacked, [S, ...] unstacked)."""
    return 1 if n_layers else 0


def cache_shardings(engine, mesh, cache: dict[str, Any]) -> dict[str, Any]:
    """NamedSharding pytree for `jax.device_put`: each sharded site's shard
    axis pins to the mesh "model" axis, every other leaf (and every unsharded
    site) replicates. Shapes are already shard-expanded by
    `ReuseEngine.init_cache`, so placement is pure axis naming — no resplit.
    """
    model_size = int(mesh.shape["model"])
    out: dict[str, Any] = {}
    replicated = NamedSharding(mesh, P())
    for name, entry in cache.items():
        n_shards = engine.shards.get(name)
        if not n_shards:
            out[name] = jax.tree.map(lambda _: replicated, entry)
            continue
        if n_shards != model_size:
            raise ValueError(
                f"site {name!r} is planned for {n_shards} shards but the "
                f"mesh model axis is {model_size} wide"
            )
        ax = shard_axis_of(engine.stacking.get(name, 0))

        def _leaf_sharding(leaf, ax=ax):
            parts: list = [None] * np.ndim(leaf)
            parts[ax] = "model"
            return NamedSharding(mesh, P(*parts))

        out[name] = jax.tree.map(_leaf_sharding, entry)
    return out


# numpy dtype name → HLO shape-prefix dtype token (hlo_parse._OP_RE groups).
_DTYPE_HLO = {
    "int8": "s8",
    "int32": "s32",
    "int64": "s64",
    "uint32": "u32",
    "float32": "f32",
    "float64": "f64",
    "bfloat16": "bf16",
    "bool": "pred",
}


def cache_shape_signatures(cache: dict[str, Any]) -> set[tuple[str, tuple]]:
    """(hlo_dtype, dims) signatures of every cache leaf — global shape AND
    (for placed arrays) the per-device shard shape, since SPMD-partitioned
    HLO names buffers by their local shapes. The no-gather assertion flags
    any all-gather/all-to-all whose operands match one of these."""
    sigs: set[tuple[str, tuple]] = set()
    for leaf in jax.tree.leaves(cache):
        dt = _DTYPE_HLO.get(np.dtype(leaf.dtype).name)
        if dt is None:
            continue
        sigs.add((dt, tuple(int(d) for d in leaf.shape)))
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            sigs.add((dt, tuple(
                int(d) for d in sharding.shard_shape(leaf.shape))))
    return sigs
