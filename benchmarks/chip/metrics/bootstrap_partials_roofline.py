"""Kernels: the least time the chip could take for the traced chunks'
bootstrap partials (two multiply-adds per replicate, example and metric,
at the bf16 peak, or the scores and partials at HBM bandwidth, the
larger), over the device time of the ``bootstrap_partials`` kernel."""

import work

#: the Pallas kernel's op in the device trace (``%bootstrap_partials.N``)
KERNELS = ("%bootstrap_partials",)


def read(ctx):
    import tracing

    if not ctx.events or not ctx.peaks:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    n_boot = ctx.mix["statistics"]["bootstrap_iterations"]
    bound = sum(
        work.roofline_seconds(
            *work.bootstrap_partials(len(rows), len(scores), n_boot), ctx.peaks
        )
        for a, _b, rows, _texts, scores in ctx.probes.chunks if t0 <= a <= t1
    )
    device = tracing.kernel_seconds(ctx.events, KERNELS)
    if bound <= 0 or device <= 0:
        return None
    return 100.0 * bound / device
