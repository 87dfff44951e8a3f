"""Jitted steps: the least time the chip could take for the traced
admissions' valid prompt rows (FLOPs at the bf16 peak or bytes at HBM
bandwidth, the larger), over the device time of every program that serves
an admission: chunk prefill, prefix-page read, page write, row init (its
key, split and zero fill) and row insert."""

import work

PROGRAMS = (
    "jit_prefill_chunk",
    "jit__read_prefix_impl", "jit__read_prefix_q_impl",
    "jit__write_pages_impl", "jit__write_pages_q_impl",
    "jit__init_one", "jit__threefry_seed", "jit__threefry_split", "jit__unstack",
    "jit__insert_impl",
)


def read(ctx):
    import tracing

    if not ctx.events or not ctx.peaks:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    prefills = [(n, s) for a, _b, n, s in ctx.probes.prefills if t0 <= a <= t1]
    if not prefills and any(t0 <= a <= t1 for a, _b, _c in ctx.probes.steps):
        # decode steps ran, so admissions did: the prefill hook no longer fires
        raise RuntimeError("prefill_roofline: decode steps but no prefills recorded")
    bound = sum(
        work.roofline_seconds(*work.prefill(ctx.config, n, s, ctx), ctx.peaks)
        for n, s in prefills
    )
    device = tracing.program_seconds(ctx.events, PROGRAMS)
    if bound <= 0 or device <= 0:
        return None
    return 100.0 * bound / device
