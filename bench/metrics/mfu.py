"""Model FLOP utilisation of the whole serve step, in %: the FLOPs of
every prompt and output token the window's steps processed (2 x the
layers' weights per token, attention over each token's live context,
the output head where logits are needed; `bench.work`), over window x
chips x the chip's peak."""

from bench.work import total_work


def read(ctx):
    if not ctx.peaks or not ctx.records:
        return None
    w = total_work(ctx.dims, ctx.records, ctx.t0, ctx.t1)
    if w.model_flops <= 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips * ctx.window_s
    return 100.0 * w.model_flops / peak
