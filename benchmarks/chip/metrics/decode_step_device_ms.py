"""Device-busy milliseconds per decode step: the union of the device's
operations inside each `bench:decode` span of the traced window, averaged
over those steps."""


def read(ctx):
    tr = ctx.trace
    spans = tr.spans_named("bench:decode", within=tr.window())
    if not spans or not tr.devices:
        return None
    return sum(tr.busy_ns(s.start, s.end) for s in spans) / len(spans) / 1e6
