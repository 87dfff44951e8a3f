"""Scheduler: the share of the traced window the scheduler spent in host
work: its ``sched.step`` spans, less the ``sched.first_token`` and
``sched.decode.sync`` spans inside them, where the host waits for the
device.  Summed over scheduler threads; the program's own spans
(``repro.obs``)."""

import spans

WAITS = ("sched.first_token", "sched.decode.sync")


def read(ctx):
    got = spans.window_spans(ctx, "scheduler_host_share")
    if got is None:
        return None
    steps = [s for s in got if s.name == "sched.step"]
    if not steps:
        raise RuntimeError("scheduler_host_share: no sched.step span in the window")
    by_sid = {s.sid: s for s in got}
    host = sum(spans.clipped(s, ctx.window) for s in steps)
    host -= sum(
        spans.clipped(s, ctx.window) for s in got
        if s.name in WAITS and spans.ancestor(s, "sched.step", by_sid) is not None
    )
    return 100.0 * host / ctx.window.seconds
