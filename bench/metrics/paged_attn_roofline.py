"""The paged-attention kernel's share of its roofline, in %: the least
time the chip could take for the decode attention the traced chunks
needed (max of FLOPs over peak FLOP/s and live K/V bytes over peak
bandwidth; `bench.work`), over the summed device time of the kernel's
events (`name="paged_attention"`) in the trace."""

from bench.work import total_work


def read(ctx):
    if ctx.trace is None or not ctx.peaks or not ctx.traced_records:
        return None
    t = ctx.trace.kernel_s("paged_attention")
    w = total_work(ctx.dims, ctx.traced_records)
    if t <= 0 or w.kernel_bytes <= 0:
        return None
    need = max(w.kernel_flops / ctx.peaks["bf16_flops_per_s"],
               w.kernel_bytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / t
