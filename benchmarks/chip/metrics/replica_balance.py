"""Service router: the fewest examples any replica completed in the traced
window over the most (each replica's ``completed`` in the service's
``replica_stats``, read at the window's two ends).  Nothing where one
replica serves."""


def read(ctx):
    a, b = ctx.window.replicas0, ctx.window.replicas1
    if len(b) < 2:
        return None
    done = [rb["completed"] - ra["completed"] for ra, rb in zip(a, b)]
    if len(done) != len(b) or max(done) <= 0:
        raise RuntimeError(f"replica_balance: replicas completed {done} in the window")
    return 100.0 * min(done) / max(done)
