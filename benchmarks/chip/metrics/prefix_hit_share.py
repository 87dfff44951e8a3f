"""Paged cache: prompt tokens served from shared prefix pages, as a share
of the prompt tokens admitted in the traced window (the counter's change
over the prompts the scheduler prefilled in it)."""


def read(ctx):
    t0, t1 = ctx.window.t0, ctx.window.t1
    admitted = sum(n for a, _b, n, _s in ctx.probes.prefills if t0 <= a <= t1)
    saved = ctx.window.stats1.get("prefix_tokens_saved", 0) - ctx.window.stats0.get(
        "prefix_tokens_saved", 0
    )
    if admitted <= 0:
        return None
    return 100.0 * saved / admitted
