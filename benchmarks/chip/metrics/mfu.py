"""Whole model over the traced window: the FLOPs the served tokens needed
(prompt rows not served from the prefix cache, and generated tokens, each
attending to its live context) over the window, as a share of the bf16
peak of the chips the cell uses."""

import work


def read(ctx):
    if not ctx.peaks:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    steps = [c for a, _b, c in ctx.probes.steps if t0 <= a <= t1]
    prefills = [(n, s) for a, _b, n, s in ctx.probes.prefills if t0 <= a <= t1]
    if not steps or not prefills:
        # every traced window of a serving cell both admits and decodes: an
        # empty list is a hook that no longer fires, not an idle model
        raise RuntimeError(
            f"mfu: {len(steps)} decode steps and {len(prefills)} prefills "
            "recorded in the window"
        )
    flops = sum(work.decode_step(ctx.config, c, ctx)[0] for c in steps)
    flops += sum(work.prefill(ctx.config, n, s, ctx)[0] for n, s in prefills)
    return 100.0 * flops / (ctx.window.seconds * ctx.chips * ctx.peaks["bf16_flops"])
