"""Device milliseconds per decode step of the output head: the leaf ops
inside the traced window's `bench:decode` spans whose scope holds the
segment `head` (the final norm and the LM-head GEMM). None where the
compiled step names no `head`."""

from chip.metrics.layer_slice_device_ms import part_ms


def read(ctx):
    return part_ms(ctx, "head", lambda parts: "head" in parts)
