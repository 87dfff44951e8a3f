"""Device: the share of the traced window in which no operation ran on the
chip (one minus the union of the op intervals over the window)."""


def read(ctx):
    import tracing

    if not ctx.events:
        return None
    return 100.0 * (1.0 - tracing.busy_seconds(ctx.events) / ctx.window.seconds)
