"""What the program records of a traced window itself: its spans
(``repro.obs``) and its ``BatcherStats`` counters, for the per-layer
metrics that read them.  Where the program has neither, as before it
had ``repro.obs``, the readers return nothing; where it has them and a
window that did the work shows none, they raise, since a span or counter
that no longer fires would otherwise read as an idle layer."""

from __future__ import annotations


def window_spans(ctx, metric: str):
    """The program's spans that overlap the window, or None if the program
    records none.  Raises if the log dropped spans in the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    log = obs.records()
    t0, t1 = ctx.window.t0, ctx.window.t1
    if log.dropped and log.first_drop <= t1:
        raise RuntimeError(
            f"{metric}: the program's span log was full and dropped "
            f"{log.dropped} spans, the first {log.first_drop - t0:.3f} s "
            "after the window opened"
        )
    return [s for s in log.spans if s.t1 > t0 and s.t0 < t1]


def clipped(span, window) -> float:
    return max(0.0, min(span.t1, window.t1) - max(span.t0, window.t0))


def ancestor(span, name: str, by_sid: dict) -> int | None:
    """The sid of the nearest enclosing span called ``name``, or None."""
    while span.parent is not None and span.parent in by_sid:
        span = by_sid[span.parent]
        if span.name == name:
            return span.sid
    return None


def per_admission_ms(ctx, key: str, metric: str):
    """A ``BatcherStats`` seconds counter's change over the window, per
    admission counted in it, in ms; None if the program has no such
    counter."""
    a, b = ctx.window.stats0, ctx.window.stats1
    if key not in b:
        return None
    admitted = b.get("admissions", 0) - a.get("admissions", 0)
    if admitted <= 0:
        raise RuntimeError(f"{metric}: no admission counted in the window")
    return 1e3 * (b[key] - a.get(key, 0.0)) / admitted
