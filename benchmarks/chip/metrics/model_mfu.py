"""Model FLOP utilisation of the window, in percent: the dense model's
operations for every prefill and decode token that the window completed
(two per linear weight per token, plus attention over the valid context;
a skipped tile is not counted as work done), over the window's seconds
times the chip's bf16 peak."""


def read(ctx):
    if ctx.peak is None:
        return None
    w, t = ctx.window, ctx.cell.traffic
    close = w.start + w.seconds
    flops = 0.0
    for s in w.steps:
        if s.end > close:
            continue
        if s.kind == "prefill":
            flops += ctx.shape.prefill_flops(s.rows, t["prompt_len"])
        else:
            flops += ctx.shape.decode_flops(s.rows, s.position)
    return 100.0 * flops / (w.seconds * ctx.peak.bf16_flops)
