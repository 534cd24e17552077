"""A new architecture is new files: the lookup, pointed at a directory that
holds a toy module, finds it by the configuration's `model_type`, and the
shared weight draw, check and head take it as they take the dense one. The
toy is the dense decoder with one more norm after every layer; the test
writes it into its own temporary directory and edits no file of the
benchmark."""

import importlib
import sys
import textwrap

import numpy as np
import pytest

from chip import arch, check, reference, weights
from chip.arch import dense
from chip.tests.conftest import tiny_cell

TOY = textwrap.dedent('''
    """The dense decoder with an RMSNorm after every layer."""

    import jax.numpy as jnp

    from chip.arch.dense import (  # noqa: F401
        block, check_program, program_fields, shape, spec_of)
    from chip.arch import dense
    from chip.reference import rms_norm


    def layout(model):
        out = dense.layout(model)
        n, d = model["num_hidden_layers"], model["hidden_size"]
        out["blocks/post_norm/scale"] = ((n, d), jnp.dtype(jnp.float32), None)
        return out


    def final_hidden(params, spec, tokens, quant=None):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        blocks = params["blocks"]
        for layer in range(blocks["post_norm"]["scale"].shape[0]):
            x = block(x, blocks, layer, spec=spec, quant=quant)
            x = rms_norm(x, blocks["post_norm"]["scale"][layer], spec[7])
        return rms_norm(x, params["final_norm"]["scale"], spec[7])
''')


@pytest.fixture
def toy(tmp_path, monkeypatch):
    model = dict(tiny_cell().conf["model"], model_type="toy_postnorm")
    # the benchmark's own directory holds no module of that name
    with pytest.raises(LookupError, match="toy_postnorm.py"):
        arch.resolve(model)
    (tmp_path / "toy_postnorm.py").write_text(TOY)
    monkeypatch.setattr(arch, "HERE", tmp_path)
    monkeypatch.setattr(arch, "__path__", [str(tmp_path)])
    importlib.invalidate_caches()
    yield model, arch.resolve(model)
    sys.modules.pop("chip.arch.toy_postnorm", None)


def test_toy_architecture_draws_its_layout(toy):
    model, family = toy
    layout = family.layout(model)
    assert set(layout) - set(dense.layout(model)) == {"blocks/post_norm/scale"}
    params = weights.make_params(layout, 41)
    scale = params["blocks"]["post_norm"]["scale"]
    assert scale.shape == (model["num_hidden_layers"], model["hidden_size"])
    # drawn as a norm weight: its offset from 1 spreads by NORM_SPREAD
    assert 0.5 < float(np.std(np.asarray(scale))) / weights.NORM_SPREAD < 2


def test_toy_reference_runs_through_checks_head(toy):
    model, family = toy
    spec = family.spec_of(model)
    params = weights.make_params(family.layout(model), 42)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    seq = list(prompt)
    for _ in range(4):
        h = family.final_hidden(params, spec, seq)
        _, arg = reference.head_max_argmax(params, h[-1:])
        seq.append(int(arg[0]))
    served = check.Served(prompt=np.array(prompt), tokens=np.array(seq[8:]),
                          finished=True)
    judged = check.served(family, spec, params, [served], ranks=True)
    assert np.max(judged["gap"]) == 0.0 and np.max(judged["rank"]) == 0.0
    # the extra norm is in the forward: the dense reference reads otherwise
    diff = family.final_hidden(params, spec, seq) - \
        dense.final_hidden(params, spec, seq)
    assert float(np.abs(np.asarray(diff)).max()) > 1e-3


def test_toy_architecture_gives_its_flops(toy):
    model, family = toy
    got = family.shape(model)
    assert got.decode_flops(8, 511) == dense.shape(model).decode_flops(8, 511)
    assert got.prefill_flops(8, 512) > got.decode_flops(8, 511) > 0
