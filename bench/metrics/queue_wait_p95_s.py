"""95th percentile of the scheduler's queue wait (`Request.queue_wait_s`:
submit to the lane's first chunk) over the requests due in the window;
one never admitted waited until the window's end."""

from bench.metrics import due_in_window, p95


def read(ctx):
    return p95([r["queue_wait"] if r["queue_wait"] is not None
                else ctx.t1 - r["submitted"] for r in due_in_window(ctx)
                if r["submitted"]])
