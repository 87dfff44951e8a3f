"""Stages: the share of the traced window spent in ``ScoreStage.run`` (a
benchmark-side span around each chunk's scoring)."""


def read(ctx):
    spans = [(a, b) for a, b, *_ in ctx.probes.chunks]
    if not spans:
        return None
    busy = ctx.union(spans, ctx.window.t0, ctx.window.t1)
    return 100.0 * busy / ctx.window.seconds
