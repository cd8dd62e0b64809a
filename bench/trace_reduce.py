"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time,
time per device operation, collective time and idle gaps by host span.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX. A TPU
trace holds one plane per chip (`/device:TPU:<n>`) whose `XLA Ops`
line has one event per executed HLO op, nested: a `while` loop's event
spans the ops of its body. Its `XLA Modules` line has one event per
program run. The host plane's `python` line holds the host's spans:
`jax.profiler.TraceAnnotation`s and JAX's own dispatch events, on the
same clock as the device.

    summary = reduce_trace("run.xplane.pb", ("paged_attention",),
                           span_prefix="bench.")

Busy time is the union of the `XLA Ops` intervals; the window is the
host span `<span_prefix>window` or, without one, the span of device
events. Per-op time is self time (an op's duration less its nested
ops'), so the per-op totals add up to the busy time and a `while` loop
does not count its body twice. A kernel's time is the duration of its own events, found by
the op's name (the Pallas kernel's `name=`).
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: HLO op names of collectives (and their async halves)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: host spans shorter than this are ignored when labelling gaps
MIN_GAP_NS = 20_000
HOST_LINE = "python"
UNLABELLED = "host: serve loop, no span"

_OP_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(\.\d+)?(\s*=|$)")


def op_name(event_name: str) -> str:
    """`%paged_attention.14 = (bf16[...]) ...` -> `paged_attention.14`."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    return head


def op_kind(event_name: str) -> str:
    """The op's name without its instance number: `paged_attention`."""
    m = _OP_NAME.match(op_name(event_name))
    return m.group(1) if m else op_name(event_name)


@dataclasses.dataclass
class DeviceTrace:
    """One chip's reduction. Times in seconds."""

    name: str
    window_s: float
    busy_s: float
    op_self_s: Dict[str, float]
    kernel_s: Dict[str, float]
    collective_s: float
    modules: List[Tuple[str, int, int]]        # (name, start_ns, end_ns)
    busy: List[Tuple[int, int]]                # merged busy intervals


@dataclasses.dataclass
class TraceSummary:
    """All chips of one trace, and the host's spans."""

    window_ns: Tuple[int, int]
    devices: List[DeviceTrace]
    host_spans: List[Tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return float(np.mean([d.busy_s for d in self.devices]))

    def kernel_s(self, kernel: str) -> float:
        """A kernel's device seconds, summed over the chips."""
        return sum(d.kernel_s.get(kernel, 0.0) for d in self.devices)

    def top_ops(self, n: int = 10) -> List[List]:
        """The device ops that took most self time, summed over chips."""
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for k, v in d.op_self_s.items():
                tot[k] += v
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Device-idle seconds of the first chip, summed by the host span
        that overlapped each gap most."""
        if not self.devices:
            return []
        tot: Dict[str, float] = collections.Counter()
        for lo, hi in _complement(self.devices[0].busy, *self.window_ns):
            if hi - lo < MIN_GAP_NS:
                continue
            tot[_label(self.host_spans, lo, hi)] += (hi - lo) / 1e9
        return [[k, v] for k, v in tot.most_common(n)]


def _merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _complement(busy, lo: int, hi: int) -> List[Tuple[int, int]]:
    gaps, cur = [], lo
    for a, b in _clip(busy, lo, hi):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def covered_ns(busy, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the merged intervals cover."""
    return sum(b - a for a, b in _clip(busy, lo, hi))


def _label(spans, lo: int, hi: int) -> str:
    best, best_ns = UNLABELLED, 0
    for name, a, b in spans:
        if b <= lo:
            continue
        if a >= hi:
            break
        ov = min(b, hi) - max(a, lo)
        if ov > best_ns:
            best, best_ns = name, ov
    return "host: " + best if best != UNLABELLED else best


def _self_times(events: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Self time per op name of properly nested events (start, end,
    name), by a stack sweep."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out: Dict[str, float] = collections.Counter()
    stack: List[List] = []          # [end, name, child_ns]

    def pop():
        end, name, child, start = stack.pop()
        out[name] += max(0, end - start - child) / 1e9

    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            pop()
        if stack:
            stack[-1][2] += end - start
        stack.append([end, name, 0, start])
    while stack:
        pop()
    return out


def _device_trace(plane, kernels: Sequence[str],
                  window: Optional[Tuple[int, int]]) -> DeviceTrace:
    ops: List[Tuple[int, int, str]] = []
    modules: List[Tuple[str, int, int]] = []
    collective_iv: List[Tuple[int, int]] = []
    kernel_ns: Dict[str, int] = collections.Counter()
    kset = set(kernels)
    for line in plane.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                s = int(e.start_ns)
                t = s + int(e.duration_ns)
                if window is None or (t > window[0] and s < window[1]):
                    modules.append((e.name, s, t))
        elif line.name in ("XLA Ops", "Async XLA Ops"):
            is_sync = line.name == "XLA Ops"
            for e in line.events:
                s = int(e.start_ns)
                t = s + int(e.duration_ns)
                if window is not None and (t <= window[0] or s >= window[1]):
                    continue
                name = op_name(e.name)
                kind = op_kind(e.name)
                if kind.startswith(COLLECTIVES):
                    collective_iv.append((s, t))
                if not is_sync:
                    continue
                ops.append((s, t, name))
                if kind in kset:
                    kernel_ns[kind] += t - s
    if window is None:
        lo = min((s for s, _, _ in ops), default=0)
        hi = max((t for _, t, _ in ops), default=0)
    else:
        lo, hi = window
    busy = _merge(_clip([(s, t) for s, t, _ in ops], lo, hi))
    return DeviceTrace(
        name=plane.name, window_s=(hi - lo) / 1e9,
        busy_s=sum(b - a for a, b in busy) / 1e9,
        op_self_s=dict(_self_times(ops)),
        kernel_s={k: v / 1e9 for k, v in kernel_ns.items()},
        collective_s=covered_ns(_merge(collective_iv), lo, hi) / 1e9,
        modules=sorted(modules, key=lambda m: m[1]), busy=busy)


def reduce_trace(path: str, kernels: Sequence[str] = (),
                 span_prefix: str = "") -> TraceSummary:
    """Reduce the trace at `path`. The window (ns, on the trace's
    clock) that bounds every reduction is the host span
    `<span_prefix>window` where there is one, else the first chip's
    device span; that span labels no idle gap. Host spans are the
    `python` line's events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host: List[Tuple[str, int, int]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name == HOST_LINE:
                    for e in line.events:
                        s = int(e.start_ns)
                        host.append((e.name, s, s + int(e.duration_ns)))
    host.sort(key=lambda h: h[1])
    window = None
    if span_prefix:
        # the window's own span covers every gap: it labels none
        mark = span_prefix + "window"
        marks = [(s, t) for n, s, t in host if n == mark]
        host = [h for h in host if h[0] != mark]
        if marks:
            window = (marks[0][0], marks[-1][1])
    devs = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    devices = [_device_trace(p, kernels, window) for p in devs]
    if window is None:
        window = (0, 0)
        if devices and devices[0].busy:
            window = (devices[0].busy[0][0], devices[0].busy[-1][1])
    return TraceSummary(window_ns=window, devices=devices, host_spans=host)


def module_gaps(dev: DeviceTrace, match: str) -> Tuple[float, int]:
    """Device-idle seconds between consecutive runs of the modules whose
    name holds `match`, and the number of such boundaries."""
    runs = [(s, t) for name, s, t in dev.modules if match in name]
    idle, n = 0, 0
    for (_, t0), (s1, _) in zip(runs, runs[1:]):
        if s1 <= t0:
            n += 1
            continue
        idle += (s1 - t0) - covered_ns(dev.busy, t0, s1)
        n += 1
    return idle / 1e9, n
