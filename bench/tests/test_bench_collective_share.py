"""`collective_share`: the chips' mean self time in collective ops over
the traced window, by hand on a two-chip summary and on the one-chip
trace recorded on a TPU v5e."""

import gzip
import os
import shutil
import types

import pytest

from bench import trace_reduce as tr
from bench.metrics import collective_share

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "serve_smoke.xplane.pb.gz")


def _chip(name, op_self_s, collective_s):
    return tr.DeviceTrace(name=name, window_s=2.0, busy_s=1.9,
                          op_self_s=op_self_s, kernel_s={},
                          collective_s=collective_s, modules=[], busy=[])


def _read(summary):
    return collective_share.read(types.SimpleNamespace(trace=summary))


def test_two_chips_by_hand():
    """Chip 0 spends 0.3 s in collectives (and holds an async span of
    1.9 s that overlaps its compute), chip 1 0.4 s, in a 2 s window."""
    chips = [_chip("/device:TPU:0", {"all-reduce.3": 0.2, "fusion.1": 1.5,
                                     "all-gather-start.2": 0.1}, 1.9),
             _chip("/device:TPU:1", {"all-reduce.3": 0.4,
                                     "paged_attention.2": 1.5}, 0.4)]
    s = tr.TraceSummary(window_ns=(0, 2_000_000_000), devices=chips,
                        host_spans=[])
    assert _read(s) == pytest.approx(100 * 0.35 / 2.0)


def test_nothing_to_read():
    assert _read(None) is None
    assert _read(tr.TraceSummary(window_ns=(0, 10), devices=[],
                                 host_spans=[])) is None


def test_recorded_one_chip_trace_reads_zero(tmp_path):
    path = tmp_path / "serve_smoke.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = tr.reduce_trace(str(path), ("paged_attention",),
                        span_prefix="bench.")
    assert len(s.devices) == 1 and s.busy_s > 0
    assert _read(s) == 0.0
