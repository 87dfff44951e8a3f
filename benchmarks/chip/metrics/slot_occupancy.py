"""Scheduler: decode slots in use per step, as a share of all slots, over
the traced window (``BatcherStats`` counters, read at its two ends)."""


def read(ctx):
    a, b = ctx.window.stats0, ctx.window.stats1
    steps = b.get("steps", 0) - a.get("steps", 0)
    slots = b.get("n_slots", 0)
    if steps <= 0 or not slots:
        return None
    active = b["active_slot_steps"] - a["active_slot_steps"]
    return 100.0 * active / (steps * slots)
