"""Output tokens per second: every token served in the window (first
tokens and decoded ones, each counted at its step's stamp), over the
window's seconds."""


def read(ctx):
    tokens = sum(r.tokens(ctx.t0, ctx.t1) for r in ctx.records)
    return tokens / ctx.window_s if tokens else None
