"""The architecture modules give the numbers the benchmark gave before they
existed: for both configuration files, the weight layout, the seeded weights,
the reference's final hidden state of one seeded request with and without the
stated site codes, and the FLOP counts at the cells' own sizes, against
`data/frozen_numbers.json`, recorded at the commit it names. Arrays compare
bitwise, through a SHA-256 of their bytes; counts compare exactly.

The reference's float32 sums depend on how many threads XLA's CPU backend
splits them over, so the numbers are read in a child process held to one
CPU core, as they were recorded. They depend on the jax and jaxlib versions
and the CPU's vector instructions too: the final hidden state compares
bitwise only where those are the ones the data file records, and its last
row compares within `ROW_TOLERANCE` of the row's largest value everywhere.
Rounding to site codes can move a code by one step where the plain sums
differ in their last bit, hence its wider tolerance."""

import hashlib
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
FROZEN = pathlib.Path(__file__).resolve().parent / "data" / \
    "frozen_numbers.json"
CONFIGS = ("qwen3-32b-l8", "nemotron-4-15b-l8")
QUANTITIES = ("layout", "layout_cpu", "params_sha256", "final_hidden",
              "decode_flops", "prefill_flops")
ROW_TOLERANCE = {"plain": 1e-5, "site_codes": 1e-3}


def environment() -> dict:
    """What XLA's CPU sums depend on besides the code: the versions and the
    CPU, named by its model and a digest of its instruction-set flags."""
    import platform

    import jax
    import jaxlib

    cpu = platform.processor()
    try:
        info = pathlib.Path("/proc/cpuinfo").read_text().splitlines()
    except OSError:
        info = []
    first = {}
    for line in info:
        key, _, value = line.partition(":")
        first.setdefault(key.strip(), value.strip())
    if "flags" in first:
        flags = " ".join(sorted(first["flags"].split()))
        cpu = (f"{first.get('model name', cpu)} flags sha256 "
               f"{hashlib.sha256(flags.encode()).hexdigest()[:16]}")
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "machine": platform.machine(), "cpu": cpu}


def _layout_json(layout):
    import numpy as np

    return {p: [list(s), np.dtype(dt).name, std]
            for p, (s, dt, std) in sorted(layout.items())}


def _digest(tree):
    import jax
    import numpy as np

    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(flat,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def record(frozen: dict) -> dict:
    """The numbers of `frozen`'s sizes, read through the architecture
    modules."""
    import numpy as np

    from chip import arch, weights

    req, sizes = frozen["request"], frozen["sizes"]
    out = {}
    for name in CONFIGS:
        conf = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                           / f"{name}.json").read_text())
        model = conf["model"]
        family = arch.resolve(model)
        small = dict(model, **frozen["cpu_size"])
        rec = {"layout": _layout_json(family.layout(model)),
               "layout_cpu": _layout_json(family.layout(small))}
        rec["params_sha256"] = {
            str(s): _digest(weights.make_params(family.layout(small), s))
            for s in frozen["seeds"]}
        params = weights.make_params(family.layout(small), frozen["seeds"][0])
        spec = family.spec_of(small)
        rng = np.random.default_rng(req["seed"])
        toks = rng.integers(0, small["vocab_size"], req["length"]) \
            .astype(np.int32)
        c = conf["precision"]["site_codes"]
        quant = (int(c["bits"]), float(c["clip"]), req["prompt_len"])
        hidden = {"plain": family.final_hidden(params, spec, toks),
                  "site_codes": family.final_hidden(params, spec, toks,
                                                    quant=quant)}
        rec["final_hidden_sha256"] = {k: _digest(h)
                                      for k, h in hidden.items()}
        rec["final_hidden_last_row"] = {
            k: [float(v) for v in np.asarray(h)[-1]]
            for k, h in hidden.items()}
        shape = family.shape(model)
        rec["decode_flops"] = [shape.decode_flops(sizes["batch"], p)
                               for p in range(*sizes["positions"])]
        rec["prefill_flops"] = shape.prefill_flops(sizes["batch"],
                                                   sizes["prompt_len"])
        out[name] = rec
    return out


@pytest.fixture(scope="module")
def frozen():
    return json.loads(FROZEN.read_text())


@pytest.fixture(scope="module")
def now(frozen):
    core = min(os.sched_getaffinity(0))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), str(ROOT / "benchmarks")]))
    code = (f"import os; os.sched_setaffinity(0, {{{core}}}); "
            "import json, sys; "
            "from chip.tests.test_frozen_numbers import environment, record; "
            "print(json.dumps({'configs': record(json.load(sys.stdin)), "
            "'environment': environment()}))")
    got = subprocess.run(
        [sys.executable, "-c", code], input=json.dumps(frozen), env=env,
        capture_output=True, text=True, timeout=300, check=True)
    return json.loads(got.stdout.splitlines()[-1])


def test_recorded_at_the_parent_commit(frozen):
    assert len(frozen["recorded_at"]) == 40
    assert set(frozen["configs"]) == set(CONFIGS)
    assert set(frozen["environment"]) == {"jax", "jaxlib", "machine", "cpu"}


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("config", CONFIGS)
def test_numbers_as_recorded(frozen, now, config, quantity):
    import numpy as np

    was, got = frozen["configs"][config], now["configs"][config]
    if quantity == "final_hidden":
        for kind, tol in ROW_TOLERANCE.items():
            row = np.asarray(was["final_hidden_last_row"][kind])
            np.testing.assert_allclose(
                got["final_hidden_last_row"][kind], row, rtol=0,
                atol=tol * float(np.abs(row).max()))
        if now["environment"] == frozen["environment"]:
            assert got["final_hidden_sha256"] == was["final_hidden_sha256"]
    else:
        assert got[quantity] == was[quantity]
