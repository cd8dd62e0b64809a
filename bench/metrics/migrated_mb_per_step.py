"""KV bytes moved between the tiers per decode step, in MB:
sum(m_in + m_out) over the window's decode steps, over their count."""


def read(ctx):
    s = ctx.step_stats
    if s.shape[0] == 0:
        return None
    return (s[:, 2].sum() + s[:, 3].sum()) / s.shape[0] / 1e6
