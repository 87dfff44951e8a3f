"""Response cache and storage: the share of the traced window spent inside
``ResponseCache.lookup`` (a benchmark-side span around each call)."""


def read(ctx):
    if not ctx.probes.lookups:
        return None
    busy = ctx.union(ctx.probes.lookups, ctx.window.t0, ctx.window.t1)
    return 100.0 * busy / ctx.window.seconds
