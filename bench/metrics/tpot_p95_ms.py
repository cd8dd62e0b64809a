"""95th percentile, over requests due in the window and served at least
two tokens, of (last token - first token) / (tokens - 1), in ms. A
request cut by the window's end takes its reap time as its last
token's."""

from bench.metrics import due_in_window, p95


def read(ctx):
    return p95([1e3 * (r["finished"] - r["first"]) / (len(r["output"]) - 1)
                for r in due_in_window(ctx)
                if len(r["output"]) >= 2 and r["first"] is not None
                and r["finished"] is not None])
