"""Device-idle milliseconds between consecutive serve-chunk programs in
the trace, per boundary: the host's work at a chunk boundary (read
back, complete, release, admit, upload) that the chip waits for."""

from bench.trace_reduce import module_gaps


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    idle, n = module_gaps(ctx.trace.devices[0], "serve_chunk")
    return 1e3 * idle / n if n else None
