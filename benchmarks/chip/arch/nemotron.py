"""Nemotron-4 (`model_type` nemotron): the dense decoder of `dense.py`,
with an ungated squared-ReLU MLP."""

from chip.arch.dense import (  # noqa: F401
    check_program,
    final_hidden,
    layout,
    program_fields,
    shape,
    spec_of,
)
