"""Scheduler: decode slots in use per step, as a share of all slots, over
the traced window (``BatcherStats`` counters, read at its two ends).  Over
replicas, each steps its own slots: the capacity is each replica's steps
times its slots, summed."""


def read(ctx):
    a, b = ctx.window.stats0, ctx.window.stats1
    parts = [
        (ra.get("batcher", {}), rb.get("batcher", {}))
        for ra, rb in zip(ctx.window.replicas0, ctx.window.replicas1)
    ] or [(a, b)]
    steps = sum(q.get("steps", 0) - p.get("steps", 0) for p, q in parts)
    capacity = sum(
        (q.get("steps", 0) - p.get("steps", 0)) * q.get("n_slots", 0) for p, q in parts
    )
    if steps <= 0 or not capacity:
        return None
    active = b["active_slot_steps"] - a["active_slot_steps"]
    return 100.0 * active / capacity
