"""Experts: the device time of the ``expert_gmm`` kernel as a share of the
device time of every program that ran in the traced window (the trace's
module events, summed over devices)."""

KERNELS = ("%expert_gmm",)


def read(ctx):
    import tracing

    if not ctx.events:
        return None
    kernel = tracing.kernel_seconds(ctx.events, KERNELS)
    total = sum(e[4] for e in ctx.events if e[1] in tracing.MODULE_LINES) / 1e9
    if kernel <= 0 or total <= 0:
        return None
    return 100.0 * kernel / total
