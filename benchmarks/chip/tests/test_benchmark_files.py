"""`BENCHMARK.json` and the files it names by convention agree: every cell
finds its configuration, traffic mix, generator and limits, and every
per-layer metric its reader, by name alone."""

import json
import pathlib
import re

import pytest

from chip import arch, check, harness, system

ROOT = pathlib.Path(__file__).resolve().parents[3]
HERE = ROOT / "benchmarks" / "chip"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_resolves_by_name():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(BENCH, w["name"], HERE)
        assert (HERE / "generators" / f"{cell.traffic['kind']}.py").is_file()
        assert cell.limits and set(cell.limits) <= set(check.NUMBERS)
        assert all(v["limit"] > 0 for v in cell.limits.values())
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_every_metric_has_a_reader_and_a_valid_name():
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.load_module(
            HERE / "metrics" / f"{m['name']}.py").read)
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.25


def test_config_files_are_what_the_program_runs():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        family = arch.resolve(conf["model"])
        assert family.__name__ == f"chip.arch.{conf['model']['model_type']}"
        cfg = system.program_config(conf, family)
        assert cfg.n_layers == conf["model"]["num_hidden_layers"]
        family.spec_of(conf["model"])


def test_unknown_model_type_names_the_file_to_add():
    conf = json.loads((HERE / "configs" / "qwen3-32b-l8.json").read_text())
    model = dict(conf["model"], model_type="no_such_family")
    with pytest.raises(LookupError, match=re.escape(
            str(HERE / "arch" / "no_such_family.py"))):
        arch.resolve(model)
    with pytest.raises(LookupError, match="no model_type"):
        arch.resolve({k: v for k, v in model.items() if k != "model_type"})
