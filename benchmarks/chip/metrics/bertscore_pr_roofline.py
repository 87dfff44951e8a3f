"""Kernels: the least time the chip could take for the traced chunks'
BERTScore similarities (every valid candidate word against every valid
reference word, 256 wide), over the device time of the ``bertscore_pr``
kernel."""

import work

KERNELS = ("%bertscore_pr",)
MAX_WORDS = 64


def read(ctx):
    import tracing

    if not ctx.events or not ctx.peaks:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    pairs = [
        (min(len(text.split()), MAX_WORDS), min(len(row["reference"].split()), MAX_WORDS))
        for a, _b, rows, texts, scores in ctx.probes.chunks
        if t0 <= a <= t1 and "bertscore" in scores
        for row, text in zip(rows, texts)
    ]
    bound = work.roofline_seconds(*work.bertscore_pr(pairs), ctx.peaks)
    device = tracing.kernel_seconds(ctx.events, KERNELS)
    if bound <= 0 or device <= 0:
        return None
    return 100.0 * bound / device
