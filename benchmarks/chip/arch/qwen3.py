"""Qwen3 (`model_type` qwen3): the dense decoder of `dense.py`, with
qk-norm and a SwiGLU MLP."""

from chip.arch.dense import (  # noqa: F401
    check_program,
    final_hidden,
    layout,
    program_fields,
    shape,
    spec_of,
)
