"""Device-busy milliseconds per wave prefill: the union of the device's
operations inside each `bench:prefill` span of the traced window, averaged
over those prefills."""


def read(ctx):
    tr = ctx.trace
    spans = tr.spans_named("bench:prefill", within=tr.window())
    if not spans or not tr.devices:
        return None
    return sum(tr.busy_ns(s.start, s.end) for s in spans) / len(spans) / 1e6
