"""Memory-bandwidth utilisation of the whole serve step, in %: the
bytes the window's steps needed (the weights once per step, the live
K/V of each lane that decoded or prefilled; `bench.work`), over window
x chips x the chip's peak bandwidth."""

from bench.work import total_work


def read(ctx):
    if not ctx.peaks or not ctx.records:
        return None
    w = total_work(ctx.dims, ctx.records, ctx.t0, ctx.t1)
    if w.step_bytes <= 0:
        return None
    peak = ctx.peaks["hbm_bytes_per_s"] * ctx.chips * ctx.window_s
    return 100.0 * w.step_bytes / peak
