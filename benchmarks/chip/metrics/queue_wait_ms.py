"""Scheduler: the mean time an example waited in the scheduler's queue
before it was admitted to a slot, over the admissions of the traced window
(``BatcherStats.queue_wait_s`` and ``admissions``, read at the window's two
ends; the program adds each wait at the admission)."""

import spans


def read(ctx):
    return spans.per_admission_ms(ctx, "queue_wait_s", "queue_wait_ms")
