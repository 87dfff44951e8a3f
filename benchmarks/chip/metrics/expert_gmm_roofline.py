"""Experts: the least time the chip could take for the traced window's
grouped matmuls (their FLOPs at the bf16 peak or their bytes at HBM
bandwidth, the larger), over the device time of the ``expert_gmm`` kernel.
The work is the family's ``expert_gmm(config, pairs, touched)`` on the
program's counters (``BatcherStats``, decode steps and prefill chunks):
the (token, held expert) pairs computed and the held experts touched, both
summed over layers, read at the window's two ends."""

import families
import work

KERNELS = ("%expert_gmm",)
PHASES = ("decode", "prefill")


def window_work(ctx):
    """(pairs, touched) over the window, or None where the program or the
    family counts none."""
    a, b = ctx.window.stats0, ctx.window.stats1
    family = families.load(ctx.config["model_type"])
    if not hasattr(family, "expert_gmm") or "expert_pairs_decode" not in b:
        return None

    def delta(name):
        return sum(b[f"{name}_{p}"] - a.get(f"{name}_{p}", 0) for p in PHASES)

    return delta("expert_pairs"), delta("experts_touched")


def read(ctx):
    import tracing

    got = window_work(ctx)
    if got is None or not ctx.events or not ctx.peaks:
        return None
    family = families.load(ctx.config["model_type"])
    bound = work.roofline_seconds(*family.expert_gmm(ctx.config, *got), ctx.peaks)
    device = tracing.kernel_seconds(ctx.events, KERNELS)
    if bound <= 0 or device <= 0:
        return None
    return 100.0 * bound / device
