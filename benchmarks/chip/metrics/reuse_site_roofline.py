"""The reuse sites' share of their roofline, in percent: the least time
that every site call of the traced window's decode steps needs (see
`work.reuse_site_job`: delta pass plus GEMM over the live tiles, at the
window's measured live share per site) over the device time of every
operation under a `reuse_site:<site>` scope in those steps. It counts the
same work whatever implements a site, so fusing the delta pass into the GEMM
or a ragged grid shows here. The per-layer weight-slice copies in front of
each site carry the layer scan's scope, not a site's, so they lie outside
this share; they show in `decode_step_device_ms` and `model_mfu`."""

from chip import work


def read(ctx):
    return site_share(ctx, work.reuse_site_job,
                      lambda name, scope: "reuse_site:" in scope)


def site_share(ctx, job, match):
    eng, tr = ctx.system.engine, ctx.trace
    steps = tr.spans_named("bench:decode", within=tr.window())
    if ctx.peak is None or eng is None or not steps:
        return None
    need = 0.0
    for name, spec in eng.sites.items():
        skipped, computed = ctx.tiles.get(name, (0, 0))
        live = computed / (skipped + computed) if skipped + computed else 1.0
        calls = max(eng.stacking.get(name, 0), 1) * len(steps)
        need += calls * job(ctx.system.batch, spec.in_features,
                            spec.out_features, live).least_seconds(ctx.peak)
    spent = tr.op_ns(steps, match) * 1e-9
    return 100.0 * need / spent if spent else None
