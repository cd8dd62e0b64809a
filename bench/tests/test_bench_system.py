"""The harness reads what the serve chunks produced, in both of the
engine's migration modes, and a warm-in leaves the window only what
follows it.

At smoke widths on the CPU: the tokens the chunk recorder reads from
the chunks' outputs are the tokens the requests were served, the
prompt tokens it reads are every prompt, and its decode steps are the
steps the engine priced into its telemetry.
"""

import numpy as np
import pytest

from bench import harness
from bench.tests import smoke


def _session(seed, **traffic):
    base = harness.load_cell(smoke.CELL)
    return harness.Session(
        smoke.CELL, seed, config=smoke.config(base[1]),
        traffic=smoke.closed_traffic(base[2], n=8, **traffic),
        peaks=smoke.PEAKS)


def _decode_steps(records, lo=None, hi=None):
    return sum(int((r.in_window(lo, hi) & (r.emitted.max(axis=1) >= 0)
                    ).sum()) for r in records)


@pytest.mark.parametrize("overlap", [False, True])
def test_recorder_reads_the_served_tokens(overlap):
    s = _session(41, overlap_migrations=overlap)
    served = s.window(s.stream(41, 60.0), 41, 60.0)
    reqs = served.requests
    assert all(r["status"] == "ok" for r in reqs)
    recs = served.records
    got = np.concatenate([np.concatenate([r.first[r.first >= 0],
                                          r.emitted[r.emitted >= 0]])
                          for r in recs])
    want = np.concatenate([np.asarray(r["output"]) for r in reqs])
    np.testing.assert_array_equal(np.sort(got), np.sort(want))
    assert sum(int(r.prefill.sum()) for r in recs) == \
        sum(len(r["prompt"]) for r in reqs)
    assert _decode_steps(recs) == served.step_stats.shape[0] > 0
    ctx = s.context(served, 60.0)
    assert ctx.window_s == 60.0
    assert harness.read_metric("output_tok_s", ctx) == pytest.approx(
        len(want) / 60.0)


def test_warm_in_leaves_the_window_what_follows_it():
    """Timed against the same serve without a warm-in, the window opens
    partway, inside a chunk, and holds the steps stamped after it."""
    s = _session(43)
    full = s.window(s.stream(43, 60.0), 43, 60.0)
    warm = 0.4 * (full.records[-1].t1 - full.start)
    s.traffic["traffic"]["warm_s"] = warm
    served = s.window(s.stream(43, 60.0), 43, 60.0)
    assert served.t0 == served.start + warm
    assert served.t1 - served.t0 == 60.0
    recs = served.records
    inside = _decode_steps(recs, served.t0, served.t1)
    assert 0 < inside == served.step_stats.shape[0] < _decode_steps(recs)
    before = sum(r.tokens(None, served.t0) for r in recs)
    after = sum(r.tokens(served.t0, None) for r in recs)
    assert before > 0 and after > 0
    assert before + after == sum(len(r["output"]) for r in served.requests)
    ctx = s.context(served, 60.0)
    assert harness.read_metric("output_tok_s", ctx) == pytest.approx(
        after / 60.0)
    # a closed loop is due when the call starts, before the window
    assert harness.read_metric("ttft_p95_s", ctx) is None
