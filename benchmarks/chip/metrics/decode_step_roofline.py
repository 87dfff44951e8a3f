"""Jitted steps: the least time the chip could take for the traced decode
steps (their FLOPs at the bf16 peak or their bytes at HBM bandwidth, the
larger), over the device time of the decode-step programs."""

import work

#: module names of the programs that run a decode step
PROGRAMS = ("jit__paged_decode_impl", "jit__paged_decode_q_impl", "jit_decode_fn")


def read(ctx):
    import tracing

    if not ctx.events or not ctx.peaks:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    bound = sum(
        work.roofline_seconds(*work.decode_step(ctx.config, ctxs, ctx), ctx.peaks)
        for a, _b, ctxs in ctx.probes.steps if t0 <= a <= t1
    )
    device = tracing.program_seconds(ctx.events, PROGRAMS)
    if bound <= 0 or device <= 0:
        return None
    return 100.0 * bound / device
