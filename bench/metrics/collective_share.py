"""Share of the traced window that the device spent in collectives, in
%, averaged over the chips: each chip's self time of its collective
ops (`trace_reduce.COLLECTIVES`, the async halves' blocking parts too)
on its op line, over the traced window. An async collective's span,
during which the chip computes, does not count. A one-chip trace has
none and reads 0."""

from bench.trace_reduce import COLLECTIVES, op_kind


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    spent = [sum(s for op, s in d.op_self_s.items()
                 if op_kind(op).startswith(COLLECTIVES))
             for d in tr.devices]
    return 100.0 * sum(spent) / len(spent) / tr.window_s
