"""Seconds from the process's start to the window's: imports, weights
made on the device, the serve chunk compiled or loaded from the compile
cache, one warm-up serve, and the traffic's warm-in (the first `warm_s`
of the serve call, which fills the lanes)."""


def read(ctx):
    return ctx.setup_s
