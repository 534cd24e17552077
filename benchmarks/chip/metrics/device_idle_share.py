"""Percent of the traced window in which no operation ran on the device."""


def read(ctx):
    w = ctx.trace.window()
    if w is None or w.end <= w.start or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns(w.start, w.end)
                    / (w.end - w.start))
