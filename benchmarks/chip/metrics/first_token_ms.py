"""Scheduler: the mean time from an example's admission to a slot until
its first token reached the host (prefix read, chunk prefill, page write
and the first sample's sync), over the admissions of the traced window
(``BatcherStats.first_token_s`` and ``admissions`` at the window's two
ends)."""

import spans


def read(ctx):
    return spans.per_admission_ms(ctx, "first_token_s", "first_token_ms")
