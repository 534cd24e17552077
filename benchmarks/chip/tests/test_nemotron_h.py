"""The Nemotron-H architecture module (`arch/nemotron_h.py`): found by the
configuration's `model_type`, laid out as the program loads its pattern
stack, its reference agreeing with the program at a tiny size, its program
fields catching a program that runs another stack, and its counts of the
SSM update and the model's operations by hand."""

import dataclasses
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import arch, calibrate_hybrid, faults, harness, system, weights
from chip.peaks import peak_for
from chip.tests.conftest import DATA, run_tiny, tiny_cell
from chip.trace_reduce import Op, Span, Trace

HERE = pathlib.Path(__file__).resolve().parents[1]
CONF = json.loads((HERE / "configs" / "nemotron-h-47b-l9.json").read_text())
TINY = json.loads((DATA / "configs" / "tiny-hybrid.json").read_text())


def test_model_type_resolves_to_nemotron_h():
    family = arch.resolve(CONF["model"])
    assert family.__name__ == "chip.arch.nemotron_h"
    assert all(callable(getattr(family, n)) for n in arch.EXPORTS
               if n != "program_fields")


def test_published_keys_at_the_top_level_are_the_model_groups():
    """The file states the published config's keys as run twice: at its top
    level, where they are held against the published config, and in the
    `model` group that the harness reads. The two copies agree."""
    own = {"name", "source", "deployment", "model", "reduced",
           "reduced_from", "why_reduced", "assumed", "precision", "program"}
    published = {k: v for k, v in CONF.items() if k not in own}
    assert published == {k: v for k, v in CONF["model"].items()
                         if k != "torch_dtype"}
    assert set(CONF["reduced"]) <= set(published)
    assert set(CONF["reduced_from"]) == set(CONF["reduced"])


@pytest.mark.parametrize("conf", [CONF, TINY], ids=["l9", "tiny"])
def test_layout_is_the_programs_parameter_tree(conf):
    from repro.models import init_params

    family = arch.resolve(conf["model"])
    cfg = system.program_config(conf, family)
    want = jax.eval_shape(init_params, cfg, jax.random.PRNGKey(0))
    flat = {"/".join(str(k.key) for k in path): (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(want)[0]}
    got = {p: (tuple(s), jnp.dtype(dt))
           for p, (s, dt, _) in family.layout(conf["model"]).items()}
    assert got == flat


def test_stage_holds_every_kind_at_published_widths():
    family = arch.resolve(CONF["model"])
    cfg = system.program_config(CONF, family)
    assert cfg.layer_pattern == "M-M-M-M*-"
    assert (cfg.d_model, cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_groups,
            cfg.ssm_state, cfg.d_ff) == (8192, 16384, 256, 8, 256, 30720)
    layout = family.layout(CONF["model"])
    size = {p: int(np.prod(s)) * jnp.dtype(dt).itemsize
            for p, (s, dt, _) in layout.items()}
    # 4 x 876.8 MB of Mamba2, 4 x 1006.6 MB of MLP, 302 MB of attention,
    # 2 x 2.15 GB of embedding and head
    assert sum(size.values()) == pytest.approx(12.13e9, rel=2e-3)
    assert layout["blocks/mamba/in_proj"][0] == (4, 8192, 37120)


def test_reference_agrees_with_the_program():
    from repro.models import forward
    from repro.models.layers import apply_norm

    family = arch.resolve(TINY["model"])
    cfg = system.program_config(TINY, family)
    params = weights.make_params(family.layout(TINY["model"]), 77)
    tokens = np.random.default_rng(3).integers(0, cfg.vocab, (1, 24))
    h, _, _, _ = forward(params, cfg, {"tokens": jnp.asarray(tokens)})
    got = apply_norm(params["final_norm"], h, cfg.norm_eps)[0]
    with jax.default_matmul_precision("highest"):
        want = family.final_hidden(params, family.spec_of(TINY["model"]),
                                   tokens[0])
    assert float(jnp.abs(want).max()) > 1.0
    # float32 on both sides: the order of the sums only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_tiny_cell_is_correct_to_rounding():
    r = run_tiny(8, config="tiny-hybrid")
    assert r["correct"], r["check"]
    # float32 served codes equal the reference's
    assert r["check"]["logit_gap_coded"]["value"] < 1e-3
    assert r["check"]["logit_rank_coded"]["value"] == 0


def test_frozen_state_makes_the_tiny_cell_incorrect():
    r = run_tiny(21, config="tiny-hybrid", fault=faults.state_unchanged)
    assert r["correct"] is False
    for n, v in r["check"].items():
        if n != "compilations_in_window":
            assert v["value"] > v["limit"], (n, r["check"])


@pytest.mark.parametrize("override", [
    {"ssm_groups": 1}, {"ssm_groups": 4},
    {"layer_pattern": "M-M-M-M-M"}, {"layer_pattern": "M-M-M-M-*"}])
def test_program_fields_catch_another_stack(override):
    family = arch.resolve(CONF["model"])
    prog = dict(CONF["program"],
                overrides={**CONF["program"]["overrides"], **override})
    with pytest.raises(ValueError, match="differs from nemotron-h-47b-l9"):
        system.program_config(dict(CONF, program=prog), family)


def test_check_program_wants_a_pattern_stack_without_rope():
    family = arch.resolve(CONF["model"])
    cfg = system.program_config(CONF, family)
    assert family.check_program(cfg) == {}
    assert family.check_program(dataclasses.replace(cfg, rope="rope"))


def test_flops_by_hand():
    shape = arch.resolve(CONF["model"]).shape(CONF["model"])
    d, di, v = 8192, 16384, 131072
    mamba = d * 37120 + di * d
    mlp = 2 * d * 30720
    attn = d * (8192 + 2 * 1024) + 8192 * d
    assert shape.layer_params() == 4 * mamba + 4 * mlp + attn
    # per token and Mamba2 layer: the conv (2 x 4 x 20480) and the scan,
    # 5 operations per state element (16384 x 256)
    ssm = 4 * (2 * 4 * 20480 + 5 * di * 256)
    assert shape.ssm_flops() == ssm
    assert shape.decode_flops(1, 511) == \
        2 * (4 * mamba + 4 * mlp + attn + d * v) + 4 * 64 * 128 * 512 + ssm
    assert shape.prefill_flops(1, 3) == 2 * (4 * mamba + 4 * mlp + attn) * 3 \
        + 2 * d * v + 4 * 64 * 128 * 6 + 3 * ssm


def test_ssm_update_job_by_hand():
    job = arch.resolve(CONF["model"]).ssm_update_job(CONF["model"], 8)
    state = 8 * 256 * 64 * 256 * 4                  # 134.2 MB of f32 state
    conv = 8 * 3 * 20480 * 2
    small = 4 * 20480 * 2 + 20480 * 4 + 3 * 256 * 4 + (8192 + 16384) * 4
    acts = 8 * (8192 + 37120) * 2 + 8 * (8192 + 16384) * 2
    assert job.bytes == 4 * (2 * state + 2 * conv + small + acts)
    # bandwidth-bound on v5e: 1.08 GB in 1.32 ms at 819 GB/s
    v5e = peak_for("TPU v5 lite")
    assert job.least_seconds(v5e) == pytest.approx(job.bytes / 819e9)
    assert job.least_seconds(v5e) == pytest.approx(1.32e-3, rel=0.01)


SSM = "jit(step)/layer_scan/layer/mamba/ssm_update/dynamic_update_slice"
SITE = ("jit(step)/layer_scan/layer/mamba/reuse_site:mamba_in/cond/"
        "branch_1_fun/jit(reuse_matmul)/reuse_matmul_output/pallas_call")


def _ctx(ssm_scope=SSM):
    ops = [Op("fusion.100", ssm_scope, 10, 40),
           Op("reuse_matmul_output.3", SITE, 40, 90),
           Op("fusion.100", ssm_scope, 110, 130),
           Op("reuse_matmul_output.3", SITE, 130, 150)]
    spans = [Span("bench:window", 0, 200, 0), Span("bench:decode", 5, 100, 1),
             Span("bench:decode", 105, 160, 1)]
    cell = types.SimpleNamespace(family=arch.resolve(CONF["model"]),
                                 conf=CONF)
    return types.SimpleNamespace(
        trace=Trace.of({"/device:TPU:0": ops}, spans), cell=cell,
        peak=peak_for("TPU v5 lite"), system=types.SimpleNamespace(batch=8))


def _read(metric, ctx):
    return harness.load_module(HERE / "metrics" / f"{metric}.py").read(ctx)


def test_ssm_update_readers():
    ctx = _ctx()
    assert _read("ssm_update_device_ms", ctx) == pytest.approx(25 / 1e6)
    job = ctx.cell.family.ssm_update_job(CONF["model"], 8)
    assert _read("ssm_update_roofline", ctx) == pytest.approx(
        100 * job.bytes / 819e9 / 25e-9)
    # a program that names no SSM update reads nothing
    plain = _ctx(ssm_scope="jit(step)/layer_scan/layer/mamba/add")
    assert _read("ssm_update_device_ms", plain) is None
    assert _read("ssm_update_roofline", plain) is None


def test_bf16_state_reference_rounds_from_its_position_on():
    family = arch.resolve(TINY["model"])
    spec = family.spec_of(TINY["model"])
    params = weights.make_params(family.layout(TINY["model"]), 77)
    tokens = np.random.default_rng(5).integers(0, spec.vocab, 24)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.final_hidden(params, spec, tokens))
        got = np.asarray(family.final_hidden(params, spec, tokens,
                                             state_from=16))
    # the state is first rounded as position 16 is carried on to 17
    np.testing.assert_array_equal(got[:17], want[:17])
    gap = np.abs(got[17:] - want[17:]).max()
    assert 0 < gap < 0.05 * np.abs(want).max()


@pytest.mark.parametrize("fault", sorted(calibrate_hybrid.FAULTS))
def test_mamba_fault_makes_the_tiny_cell_incorrect(fault):
    r = run_tiny(21, config="tiny-hybrid",
                 fault=calibrate_hybrid.FAULTS[fault])
    assert r["correct"] is False, r["check"]
    assert r["check"]["logit_rank_coded"]["value"] > \
        r["check"]["logit_rank_coded"]["limit"], r["check"]


def test_state_control_reads_each_number(monkeypatch):
    from chip import calibrate, check

    monkeypatch.setattr(check, "control", calibrate_hybrid.state_control)
    cell = tiny_cell("tiny-hybrid")
    (row,) = calibrate.readings(cell, [8], require_chip=False)
    for n in check.NUMBERS:
        assert np.isfinite(row[f"{n}.program"]), n
        assert np.isfinite(row[f"{n}.control"]), n
    # float32 weights and codes: the program is the reference to rounding,
    # and the bfloat16 state moves the control further than that
    assert row["logit_gap_coded_mean.control"] >= \
        row["logit_gap_coded_mean.program"]
