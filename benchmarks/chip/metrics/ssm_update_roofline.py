"""The SSM update's share of its roofline, in percent: the least time one
decode step's SSM update of every Mamba2 layer needs (the architecture
module's `ssm_update_job`: float32 state in and out, conv state, small
parameters and activations, over the chip's HBM and bf16 peaks) over its
device time per decode step (`ssm_update_device_ms`). None where the
architecture has no SSM update or the step names none."""

from chip.metrics import ssm_update_device_ms


def read(ctx):
    job = getattr(ctx.cell.family, "ssm_update_job", None)
    ms = ssm_update_device_ms.read(ctx)
    if ctx.peak is None or job is None or not ms:
        return None
    need = job(ctx.cell.conf["model"], ctx.system.batch)
    return 100.0 * need.least_seconds(ctx.peak) / (ms * 1e-3)
