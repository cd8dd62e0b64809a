"""Share of the decode steps' KV reads served from the HBM tier, in %:
sum(h_read) / sum(h_read + e_read) over the window's decode steps (the
engine's `StepStats`)."""


def read(ctx):
    s = ctx.step_stats
    if s.shape[0] == 0:
        return None
    total = s[:, 0].sum() + s[:, 1].sum()
    return 100.0 * s[:, 0].sum() / total if total > 0 else None
