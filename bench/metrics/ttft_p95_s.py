"""95th percentile of the time to first token over every request due
in the window, from the time it was due. A request with no first token
by the window's end enters at (end - due)."""

from bench.metrics import due_in_window, p95


def read(ctx):
    return p95([(r["first"] if r["first"] is not None else ctx.t1)
                - r["due"] for r in due_in_window(ctx)])
