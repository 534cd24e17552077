"""Sharded reuse serving invariants (repro.dist + launch.mesh).

The load-bearing property of model-axis cache sharding: sharding is an
EXECUTION layout, never a semantics change. Outputs are bitwise-identical to
the unsharded engine, and per-shard sensor counters are DISJOINT slices of
the dense-baseline accounting (the ownership partition in
repro.sensor.counters), so their plain sum reproduces the unsharded counters
bitwise. On a real mesh (8 mocked host devices in CI) the compiled donated
step must additionally be gather-free on cache buffers — the hot-path
invariant `roofline.hlo_parse.cache_collective_violations` proves on HLO.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import ReuseEngine
from repro.sensor.counters import COUNTER_SHARD_REDUCE

try:  # property sweep runs where hypothesis exists; the deterministic
    # matrix below keeps full coverage on hosts without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def collapse_shard_lanes(sensor, axis=0):
    """Sum/first per counter over the shard axis — the mesh reduce, on host."""
    host = jax.device_get(sensor)
    return {
        key: (np.asarray(v).sum(axis=axis)
              if COUNTER_SHARD_REDUCE.get(key, "first") == "sum"
              else np.take(np.asarray(v), 0, axis=axis))
        for key, v in host.items()
    }


def run_stream(n_shards, exec_path, skip, seed, *, steps=4, b=2, k=256,
               n=128, bm=4, bk=32, n_layers=0):
    """A similarity-controlled stream through one site; returns (outs, entry,
    engine). skip is the per-element keep probability between steps."""
    rng = np.random.default_rng(seed)
    eng = ReuseEngine(impl="jnp")
    eng.register("site", k, n, block_m=bm, block_k=bk, n_layers=n_layers)
    if exec_path != "auto":
        eng.sites["site"] = dataclasses.replace(
            eng.sites["site"], exec_path=exec_path)
    if n_shards > 1:
        eng.shard_sites(n_shards)
    entry = eng.init_cache(batch=b)["site"]
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * 0.1)
    x = rng.normal(size=(b, k)).astype(np.float32)
    outs = []
    for _ in range(steps):
        keep = rng.random((b, k)) < skip
        x = np.where(keep, x, rng.normal(size=(b, k)).astype(np.float32))
        out, entry, _ = eng.apply("site", jnp.asarray(x), w, None, entry)
        outs.append(np.asarray(out))
    return outs, entry, eng


# ------------------------------------------------ the central shard property

def _assert_shard_parity(skip, exec_path, n_shards, seed):
    """Per-shard counters summed across the mesh == unsharded counters,
    BITWISE — and outputs bitwise too."""
    outs_1, entry_1, _ = run_stream(1, exec_path, skip, seed)
    outs_s, entry_s, _ = run_stream(n_shards, exec_path, skip, seed)
    for a, b in zip(outs_1, outs_s):
        assert (a == b).all()
    collapsed = collapse_shard_lanes(entry_s["sensor"])
    base = jax.device_get(entry_1["sensor"])
    for key in collapsed:
        assert np.array_equal(np.asarray(base[key]), collapsed[key]), key


@pytest.mark.parametrize("skip", [0.0, 0.5, 0.9])
@pytest.mark.parametrize("exec_path", ["dense", "compact"])
def test_shard_sum_is_unsharded_bitwise(skip, exec_path):
    """The full skip-regime × exec-path matrix, deterministically — every
    combination must hold bitwise at 4-way sharding."""
    _assert_shard_parity(skip, exec_path, 4, seed=1)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(skip=st.sampled_from([0.0, 0.5, 0.9]),
           exec_path=st.sampled_from(["dense", "compact"]),
           n_shards=st.sampled_from([2, 4]),
           seed=st.integers(0, 2**16))
    def test_shard_sum_is_unsharded_bitwise_property(
            skip, exec_path, n_shards, seed):
        """Randomized streams over the same matrix (hypothesis hosts only)."""
        _assert_shard_parity(skip, exec_path, n_shards, seed)


@pytest.mark.parametrize("exec_path", ["kernel", "ragged"])
def test_shard_parity_masked_and_ragged_paths(exec_path):
    """The masked-grid and ragged compacted-grid paths hold the same bitwise
    parity (single deterministic point; the hypothesis sweep covers
    dense/compact broadly)."""
    outs_1, entry_1, _ = run_stream(1, exec_path, 0.5, 7)
    outs_4, entry_4, _ = run_stream(4, exec_path, 0.5, 7)
    for a, b in zip(outs_1, outs_4):
        assert (a == b).all()
    collapsed = collapse_shard_lanes(entry_4["sensor"])
    base = jax.device_get(entry_1["sensor"])
    for key in collapsed:
        assert np.array_equal(np.asarray(base[key]), collapsed[key]), key


def test_stacked_site_shard_parity():
    """Stacked sites put the shard axis INSIDE the layer axis ([L, S, ...]):
    the caller's layer scan slices the leading axis exactly as before, the
    layer body sees a clean [S, ...] shard block, and the bitwise invariant
    holds per layer."""
    b, k, n, n_layers = 2, 256, 128, 2

    def run(n_shards):
        rng = np.random.default_rng(3)
        eng = ReuseEngine(impl="jnp")
        eng.register("site", k, n, block_m=4, block_k=32, n_layers=n_layers)
        eng.sites["site"] = dataclasses.replace(
            eng.sites["site"], exec_path="dense")
        if n_shards > 1:
            eng.shard_sites(n_shards)
        entry = eng.init_cache(batch=b)["site"]
        ws = [jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * 0.1)
              for _ in range(n_layers)]
        x = rng.normal(size=(b, k)).astype(np.float32)
        outs = []
        for _ in range(4):
            keep = rng.random((b, k)) < 0.5
            x = np.where(keep, x, rng.normal(size=(b, k)).astype(np.float32))
            for layer in range(n_layers):  # the caller-side layer scan
                lentry = jax.tree.map(lambda a, l=layer: a[l], entry)
                out, lentry, _ = eng.apply(
                    "site", jnp.asarray(x), ws[layer], None, lentry)
                entry = jax.tree.map(
                    lambda full, part, l=layer: full.at[l].set(part),
                    entry, lentry)
                outs.append(np.asarray(out))
        return outs, entry

    outs_1, entry_1 = run(1)
    outs_2, entry_2 = run(2)
    for a, c in zip(outs_1, outs_2):
        assert (a == c).all()
    collapsed = collapse_shard_lanes(entry_2["sensor"], axis=1)
    base = jax.device_get(entry_1["sensor"])
    for key in collapsed:
        assert np.array_equal(np.asarray(base[key]), collapsed[key]), key


def test_snapshot_reduce_and_ici_metering():
    """The ctrl snapshot's shard sums ARE the cross-mesh reduce: global
    skipped/computed match the unsharded snapshot, per-shard lanes ride
    along, and the payload is metered into ici_reduce_bytes (unsharded
    engines meter nothing)."""
    _, entry_1, eng_1 = run_stream(1, "dense", 0.5, 5)
    _, entry_4, eng_4 = run_stream(4, "dense", 0.5, 5)
    snap_1 = eng_1.ctrl_snapshot({"site": entry_1})
    snap_4 = eng_4.ctrl_snapshot({"site": entry_4})
    assert int(snap_1["site"]["skipped"]) == int(snap_4["site"]["skipped"])
    assert int(snap_1["site"]["computed"]) == int(snap_4["site"]["computed"])
    shard_sk = np.asarray(snap_4["site"]["skipped_shard"])
    assert shard_sk.shape == (4,)
    assert int(shard_sk.sum()) == int(snap_4["site"]["skipped"])
    assert "skipped_shard" not in snap_1["site"]
    assert eng_1.ici_reduce_bytes == 0.0
    assert eng_4.ici_reduce_bytes > 0.0


def test_shard_sites_validates_divisibility():
    eng = ReuseEngine(impl="jnp")
    eng.register("site", 256, 100, block_m=4, block_k=32)
    with pytest.raises(ValueError, match="not\\s+divisible|divisible"):
        eng.shard_sites(3)


# ------------------------------------------------------- mesh spec parsing

def test_mesh_spec_parser_errors():
    from repro.launch.mesh import make_host_mesh, parse_mesh_spec

    with pytest.raises(ValueError, match="unknown mesh spec"):
        parse_mesh_spec("ring:4")
    with pytest.raises(ValueError, match="not an\\s+integer|integer"):
        parse_mesh_spec("host:abc")
    with pytest.raises(ValueError, match="not an\\s+integer|integer"):
        parse_mesh_spec("host:8@x")
    with pytest.raises(ValueError, match="divide"):
        make_host_mesh(8, 3)
    with pytest.raises(ValueError, match=">= 1"):
        make_host_mesh(0)
    # more devices than this host mocks: the error must name the fix
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_host_mesh(4096)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
def test_host_mesh_shapes():
    from repro.launch.mesh import mesh_axes, parse_mesh_spec

    mesh = parse_mesh_spec("host:8")
    assert dict(mesh.shape) == {"data": 1, "model": 8}
    mesh = parse_mesh_spec("host:8@4")
    assert dict(mesh.shape) == {"data": 2, "model": 4}
    ax = mesh_axes(mesh)
    assert ax["model_size"] == 4 and ax["data_size"] == 2


# ------------------------------------------------------ cost-model pricing

def test_cost_model_unsharded_energy_unchanged():
    """A report without ici keys prices EXACTLY as before the E_ICI term:
    same keys, same values (the regression the satellite pins)."""
    from types import SimpleNamespace

    from repro.sensor.cost_model import E_HBM, E_MAC, E_ICI, sensor_energy

    model = {"total_macs": 1e9, "total_weight_bytes": 2e8,
             "skipped_macs": 4e8, "skipped_weight_bytes": 8e7}
    e = sensor_energy(SimpleNamespace(model=model))
    base = 2.0 * 1e9 * E_MAC + 2e8 * E_HBM
    saved = 2.0 * 4e8 * E_MAC + 8e7 * E_HBM
    assert e["baseline_dynamic_j"] == base
    assert e["measured_dynamic_j"] == base - saved
    assert e["saved_dynamic_j"] == saved
    assert e["dynamic_reduction"] == saved / base
    assert "ici_j" not in e and "ici_bytes" not in e

    sharded = dict(model, ici_reduce_bytes=1e6, ici_ctrl_write_bytes=5e5,
                   mesh_model_shards=8)
    es = sensor_energy(SimpleNamespace(model=sharded))
    ici_j = 1.5e6 * E_ICI
    assert es["ici_bytes"] == 1.5e6
    assert es["ici_j"] == ici_j
    assert es["measured_dynamic_j"] == base - saved + ici_j
    assert es["saved_dynamic_j"] == saved - ici_j
    assert es["baseline_dynamic_j"] == base  # baseline never pays ICI


def test_build_report_prices_sharded_ici():
    """An end-to-end sharded report carries the mesh provenance keys and an
    energy row the unsharded report does not — while the unsharded report's
    model dict has no ici/mesh keys at all."""
    _, entry_1, eng_1 = run_stream(1, "dense", 0.5, 9)
    _, entry_4, eng_4 = run_stream(4, "dense", 0.5, 9)
    eng_4.ctrl_snapshot({"site": entry_4})  # meter one window's reduce
    rep_1 = eng_1.sensor_report({"site": entry_1})
    rep_4 = eng_4.sensor_report({"site": entry_4})
    assert "mesh_model_shards" not in rep_1.model
    assert "ici_reduce_bytes" not in rep_1.model
    assert rep_4.model["mesh_model_shards"] == 4
    assert rep_4.model["ici_reduce_bytes"] > 0.0
    # counter truth is shard-invariant
    assert rep_1.model["skipped_tiles"] == rep_4.model["skipped_tiles"]
    assert rep_1.model["computed_macs"] == rep_4.model["computed_macs"]
    from repro.sensor.cost_model import sensor_energy

    assert "ici_j" in sensor_energy(rep_4)
    assert "ici_j" not in sensor_energy(rep_1)


# ------------------------------------------------------- journal v5 / replay

def _shard_row(shard, before, after, interval=1, site="s"):
    return {"kind": "decision", "decision_kind": "shard", "site": site,
            "field": "skip_rate", "layer": None, "shard": shard,
            "before": before, "after": after, "interval": interval,
            "step": interval * 4, "reason": "windowed cross-mesh reduce"}


def test_replay_chains_per_shard_and_detects_forged_shard():
    """Per-shard rows chain independently; a row whose shard id was forged
    (its `before` belongs to ANOTHER shard's trajectory) breaks its chain's
    continuity and surfaces as a mismatch naming the shard."""
    from repro.control.replay import replay_rows

    good = [
        _shard_row(0, None, 0.5),
        _shard_row(1, None, 0.1),
        _shard_row(0, 0.5, 0.6, interval=2),
        _shard_row(1, 0.1, 0.2, interval=2),
    ]
    res = replay_rows(good)
    assert res.ok and res.n_shard_scoped == 4
    assert res.final_state[("s", "shard", "skip_rate", None, 0)] == 0.6

    # shard-0's trajectory (before=0.5) journaled under shard=1: forged
    forged = good[:2] + [_shard_row(1, 0.5, 0.6, interval=2)]
    res = replay_rows(forged)
    assert not res.ok
    [m] = res.mismatches
    assert m["shard"] == 1 and m["before"] == 0.5 and m["replayed"] == 0.1
    assert "#s1" in "\n".join(res.summary_lines())


def test_journal_v5_roundtrip_and_old_versions_default_shard_none(tmp_path):
    """load_journal accepts v5 shard-stamped rows and keeps loading v1-v4
    rows with shard=None."""
    from repro.control.report import (
        CONTROL_JOURNAL_SCHEMA_VERSION,
        ControlReport,
        Decision,
        DecisionJournal,
        load_journal,
    )

    assert CONTROL_JOURNAL_SCHEMA_VERSION == 5
    p = tmp_path / "j.jsonl"
    j = DecisionJournal(str(p))
    j.append(ControlReport(
        step=4, interval=1, window_steps={"s": 4}, retrace={},
        decisions=[Decision(step=4, site="s", kind="shard",
                            field="skip_rate", before=None, after=0.25,
                            shard=2, reason="window")]))
    v4 = {"kind": "decision", "schema_version": 4, "site": "s",
          "decision_kind": "retune", "field": "sim_threshold",
          "before": 0.1, "after": 0.2, "layer": 1, "interval": 1, "step": 4,
          "reason": "r"}
    with open(p, "a") as f:
        f.write(json.dumps(v4) + "\n")
    rows = load_journal(str(p))
    decisions = [r for r in rows if r["kind"] == "decision"]
    assert decisions[0]["shard"] == 2
    assert decisions[1]["shard"] is None  # pre-v5 rows: mesh-global scope
    from repro.control.replay import replay_rows

    assert replay_rows(rows).ok


# --------------------------------------------- mocked-mesh serve-step truth

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
def test_mesh_placed_step_parity_and_no_gather():
    """On a real (mocked 8-device) mesh: the donated jitted step over a
    device_put-sharded cache produces bitwise-identical outputs and
    shard-summed counters vs the unsharded oracle, and its compiled HLO has
    zero all-gather/all-to-all touching cache buffers."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.dist.shard import cache_shape_signatures, cache_shardings
    from repro.launch.mesh import parse_mesh_spec
    from repro.roofline.hlo_parse import cache_collective_violations

    mesh = parse_mesh_spec("host:8")
    k, n, b, bm, bk = 1024, 512, 2, 4, 128
    rng = np.random.default_rng(0)
    w_np = rng.integers(-3, 4, size=(k, n)).astype(np.float32)

    def build(n_shards):
        eng = ReuseEngine(impl="jnp")
        eng.register("site", k, n, block_m=bm, block_k=bk)
        if n_shards > 1:
            eng.shard_sites(n_shards)
        return eng, eng.init_cache(batch=b)

    eng_1, cache_1 = build(1)
    eng_8, cache_8 = build(8)
    cache_8 = jax.device_put(cache_8, cache_shardings(eng_8, mesh, cache_8))
    replicated = NamedSharding(mesh, PartitionSpec())
    w_1 = jnp.asarray(w_np)
    w_8 = jax.device_put(w_1, replicated)

    def make_step(eng):
        @functools.partial(jax.jit, donate_argnums=(2,))
        def step(x, w, entry):
            out, entry, _ = eng.apply("site", x, w, None, entry)
            return out, entry

        return step

    step_1, step_8 = make_step(eng_1), make_step(eng_8)
    entry_1, entry_8 = cache_1["site"], cache_8["site"]

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    x0 = jax.device_put(
        jnp.asarray(rng.integers(-2, 3, size=(b, k)).astype(np.float32)),
        replicated)
    hlo = step_8.lower(
        aval(x0), aval(w_8), jax.tree.map(aval, entry_8)).compile().as_text()
    violations = cache_collective_violations(
        hlo, cache_shape_signatures(entry_8))
    assert not violations, violations

    x = np.asarray(x0)
    for _ in range(4):
        keep = rng.random((b, k)) < 0.5
        x = np.where(keep, x, rng.integers(-2, 3, size=(b, k)).astype(
            np.float32))
        xj = jnp.asarray(x)
        out_1, entry_1 = step_1(xj, w_1, entry_1)
        out_8, entry_8 = step_8(
            jax.device_put(xj, replicated), w_8, entry_8)
        assert (np.asarray(out_1) == np.asarray(out_8)).all()

    collapsed = collapse_shard_lanes(entry_8["sensor"])
    base = jax.device_get(entry_1["sensor"])
    for key in collapsed:
        assert np.array_equal(np.asarray(base[key]), collapsed[key]), key


# Collective lines recorded from the sharded qwen3-32b decode step compiled
# for a described v5e:2x2 mesh (backend_config trimmed). TPU layouts carry
# parentheses (`T(8,128)(2,1)`), and an async collective-permute returns the
# tuple (operand, result, contexts).
_TPU_COLLECTIVES_HLO = r"""
  %all-reduce.9 = f32[8]{0:T(128)S(1)} all-reduce(%fusion.220), channel_id=1, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%region_1.0.clone, metadata={op_name="jit(<lambda>)/while/body/closed_call/reduce_sum" stack_frame_id=18}
  %all-gather.43 = bf16[8,1,5120]{2,0,1:T(8,128)(2,1)S(1)} all-gather(%fusion.221), channel_id=2, replica_groups=[1,4]<=[4], dimensions={2}, use_global_device_ids=true, frontend_attributes={async_collective_name="all-gather-start"}, metadata={op_name="jit(<lambda>)/while/body/closed_call/convert_element_type" stack_frame_id=23}, backend_config={"collective_algorithm_config":{"emitter":"2DAllGatherOnMajorDim","debug":"\ngroup_size = 4 \nshard_size = 20480 bytes "}}
  %collective-permute-start.6 = (bf16[8,1,1536]{2,0,1:T(8,128)(2,1)S(1)}, bf16[8,1,1536]{2,0,1:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%slice.154), channel_id=9, source_target_pairs={{0,1},{1,2},{2,3}}, metadata={op_name="jit(<lambda>)/while/body/closed_call/split" stack_frame_id=84}
  %collective-permute-done.6 = bf16[8,1,1536]{2,0,1:T(8,128)(2,1)S(1)} collective-permute-done(%collective-permute-start.6), metadata={op_name="jit(<lambda>)/while/body/closed_call/split" stack_frame_id=84}
"""


def test_hlo_parse_reads_tpu_collectives():
    from repro.roofline.hlo_parse import (
        cache_collective_violations,
        iter_collectives,
        parse_collective_bytes,
    )

    assert list(iter_collectives(_TPU_COLLECTIVES_HLO)) == [
        ("all-reduce.9", "all-reduce", [("f32", (8,))]),
        ("all-gather.43", "all-gather", [("bf16", (8, 1, 5120))]),
        ("collective-permute-start.6", "collective-permute",
         [("bf16", (8, 1, 1536))]),
    ]
    assert parse_collective_bytes(_TPU_COLLECTIVES_HLO) == {
        "total_bytes": 32 + 8 * 5120 * 2 + 8 * 1536 * 2,
        "by_kind": {"all-reduce": 32, "all-gather": 8 * 5120 * 2,
                    "collective-permute": 8 * 1536 * 2},
        "count": 3,
    }
    # a gather whose result has a cache leaf's shape is a violation; the
    # permute moves the same shape but is not a gather
    sigs = {("bf16", (8, 1, 5120)), ("bf16", (8, 1, 1536))}
    assert cache_collective_violations(_TPU_COLLECTIVES_HLO, sigs) == [
        {"op": "all-gather.43", "kind": "all-gather", "dtype": "bf16",
         "dims": (8, 1, 5120)},
    ]
