"""Every architecture-specific piece of the benchmark, found by name.

A configuration file's published `model.model_type` names the module
`arch/<model_type>.py`, which exports:

  layout(model)       {path: (shape, dtype, std)} of every parameter, as the
                      program loads them; std None marks a norm weight,
                      stored as its offset from 1. `weights.make_params`
                      draws it. The output head is `lm_head` [d, V], or the
                      embedding `embed` [V, d] where there is no `lm_head`
                      (`reference.head`).
  spec_of(model)      the hashable sizes the reference needs
  final_hidden(params, spec, tokens, quant=None)
                      the float32 reference of one request up to and with
                      the final norm, [T, d]; `quant` = (bits, clip, start)
                      rounds the input of each linear reuse site to signed
                      `bits`-bit codes over [-clip, clip] at the positions
                      from `start` on (`reference.round_codes`)
  shape(model)        an object with decode_flops(batch, position) and
                      prefill_flops(batch, length): the operations the
                      model needs, the yardstick of `model_mfu`
  program_fields      {published key: (program field, how the file's value
                      reads as the field's)}
  check_program(cfg)  {what: (program, file)} of the structural ways the
                      program's config differs from the architecture

The shared pieces stay outside: the draw of `weights.make_params`, the
reference's building blocks and vocabulary-blocked head, `work.py`'s jobs.
"""

from __future__ import annotations

import importlib
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
EXPORTS = ("layout", "spec_of", "final_hidden", "shape", "program_fields",
           "check_program")


def resolve(model: dict):
    """The architecture module of a configuration's `model` group. A
    `model_type` with no module, or a module without the interface, fails
    here, naming the file."""
    kind = model.get("model_type")
    if not kind:
        raise LookupError("the configuration's model group has no "
                          "model_type, which names its architecture module")
    path = HERE / f"{kind}.py"
    if not path.is_file():
        raise LookupError(f"no architecture module for model_type {kind!r}: "
                          f"add {path}")
    module = importlib.import_module(f"{__name__}.{kind}")
    missing = [n for n in EXPORTS if not hasattr(module, n)]
    if missing:
        raise LookupError(f"{path} lacks {missing} of the architecture "
                          "interface")
    return module
