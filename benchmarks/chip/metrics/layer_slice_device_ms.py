"""Device milliseconds per decode step of the layer scan's own work: the
leaf ops inside the traced window's `bench:decode` spans whose scope holds
the segment `layer_scan` and not the segment `layer`. That is the scan's
per-layer slicing and write-back of its operands (each layer's weights, KV
state and reuse cache), which the program leaves outside the scope of the
layer it computes. None where the compiled step names no `layer_scan`."""


def read(ctx):
    return part_ms(ctx, "layer_scan",
                   lambda parts: "layer_scan" in parts and "layer" not in parts)


def part_ms(ctx, part, match):
    """Device milliseconds per decode step of the leaf ops whose scope's
    `/`-separated segments `match` accepts, or None where no op of the
    trace has a scope with the segment `part`."""
    tr = ctx.trace
    steps = tr.spans_named("bench:decode", within=tr.window())
    if not steps or not tr.devices:
        return None
    if not any(part in scope.split("/") for d in tr.devices.values()
               for _, scope in d.keys):
        return None
    ns = tr.op_ns(steps, lambda name, scope: match(scope.split("/")))
    return ns / len(steps) / 1e6
