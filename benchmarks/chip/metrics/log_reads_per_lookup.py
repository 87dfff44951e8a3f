"""Response cache and storage: commit-log files the table opened per
response-cache lookup: the ``files`` of the ``storage.read_log`` spans
inside each ``cache.lookup`` span that began in the traced window, over
the number of those lookups (the program's own spans, ``repro.obs``)."""

import spans


def read(ctx):
    got = spans.window_spans(ctx, "log_reads_per_lookup")
    if got is None:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    lookups = {s.sid for s in got if s.name == "cache.lookup" and t0 <= s.t0 <= t1}
    if not lookups:
        raise RuntimeError("log_reads_per_lookup: no cache.lookup span in the window")
    by_sid = {s.sid: s for s in got}
    files = sum(
        s.attrs.get("files", 0) for s in got
        if s.name == "storage.read_log"
        and spans.ancestor(s, "cache.lookup", by_sid) in lookups
    )
    return files / len(lookups)
