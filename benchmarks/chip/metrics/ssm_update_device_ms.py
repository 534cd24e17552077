"""Device milliseconds per decode step of the Mamba2 mixers' SSM update: the
leaf ops inside the traced window's `bench:decode` spans whose scope holds
the segment `ssm_update` (the pre-norm, the conv, the recurrence with the
state's read and in-place write, the readout and the gated group norm; all
of a mixer but its two reuse sites). None where the compiled step names no
`ssm_update`."""

from chip.metrics.layer_slice_device_ms import part_ms


def read(ctx):
    return part_ms(ctx, "ssm_update", lambda parts: "ssm_update" in parts)
