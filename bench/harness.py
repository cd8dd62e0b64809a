"""One run of one cell: set-up, the measured window, the check, the
metrics and the result line.

Everything a cell needs is found by name from `BENCHMARK.json`: the
configuration file and its reference family, the traffic file, and one
module per metric under `metrics/`. A metric module defines
`read(ctx: RunContext) -> float | None`; None leaves the metric out of
the line (nothing to read in this run).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: seconds at the end of the window that a `--trace 1` run records
TRACE_SECONDS = 12.0
#: the Pallas kernel whose roofline share the benchmark reports
KERNELS = ("paged_attention",)
#: served tokens the check compares, and the most requests it runs
CHECK_TOKENS = 400
CHECK_REQUESTS = 8
#: statuses whose tokens the check may compare: finished, or cut by the
#: window's end (every token it was served is final)
SERVED = ("ok", "timeout")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT):
    """(cell, configuration file, traffic file, BENCHMARK.json) of the
    workload `name`."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root, conf["file"])
    traffic = load_json(root, bench["paths"][0], "traffic",
                        cell["traffic"] + ".json")
    return cell, config, traffic, bench


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a cell reports: its end-to-end metrics, or with a
    trace its per-layer metrics (an entry without `workloads` is every
    cell's)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks_for(kind: str) -> Dict:
    """The chip's published peaks; an unlisted kind is an error."""
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def check_seq_len(traffic: Dict, block: int = 512) -> int:
    t = traffic["traffic"]
    n = t["prompt"]["hi"] + t["output"]["hi"]
    return -(-n // block) * block


@dataclasses.dataclass
class RunContext:
    """What a metric module reads of one window, which opens at `t0`
    and ends at `t1` (host `time.time()` seconds, as every time here).
    `records` are the serve call's chunks (the warm-in's and the
    reaping past the window's end too: count their steps by stamp),
    `step_stats` the window's decode steps, `requests` every request
    of the call."""

    cell: str
    config: Dict
    traffic: Dict
    dims: object                      # work.Dims
    peaks: Optional[Dict]
    chips: int
    seconds: float
    setup_s: float
    window_s: float
    t0: float
    t1: float
    requests: List[Dict]
    step_stats: np.ndarray            # [decode steps, 4]
    records: List                     # work.ChunkRecord, the whole call
    traced_records: List              # the chunks inside the trace
    trace: object = None              # trace_reduce.TraceSummary


def read_metric(name: str, ctx: RunContext) -> Optional[float]:
    mod = importlib.import_module(f"bench.metrics.{name}")
    v = mod.read(ctx)
    return None if v is None else float(v)


class Tracer:
    """Records the last `TRACE_SECONDS` of the window: the profiler
    starts at the first chunk boundary past `arm()`'s time and stops
    after the window, so stopping it stalls no request."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.start_at = None
        self.started_at = None
        self._span = None

    def arm(self, at: float) -> None:
        self.start_at = at

    def on_chunk(self, now: float) -> None:
        if self.started_at is not None or self.start_at is None \
                or now < self.start_at:
            return
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = self.jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.started_at = time.time()

    def stop(self) -> Optional[str]:
        if self.started_at is None:
            return None
        self._span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        found = []
        for d, _, files in os.walk(self.dir):
            found += [os.path.join(d, f) for f in files
                      if f.endswith(".xplane.pb")]
        return sorted(found)[-1] if found else None

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _device_summary(jax, devices) -> Dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


class Session:
    """One cell's system under test, set up: weights made from the seed,
    the serve chunk compiled, one warm-up serve. `config`/`traffic`/
    `peaks` replace the cell's own (the tests run a cell at a small
    size on the CPU); otherwise the peaks of the chip found are
    required."""

    def __init__(self, name: str, seed: int, *, root: str = ROOT,
                 config: Optional[Dict] = None,
                 traffic: Optional[Dict] = None,
                 peaks: Optional[Dict] = None):
        import jax
        from bench import system, weights, work

        self.jax = jax
        self.name = name
        self.cell, conf_file, traffic_file, self.bench = load_cell(name, root)
        self.config = config or conf_file
        self.traffic = traffic or traffic_file
        self.devices = jax.devices()[:self.cell["chips"]]
        self.peaks = peaks if peaks is not None \
            else peaks_for(self.devices[0].device_kind)
        self.sizes = self.config["sizes"]
        self.dims = work.Dims.from_config(self.config)
        self.mesh = system.make_mesh(self.config)
        model = system.Model(system.model_config(self.config))
        self._shardings = system.weight_shardings(model, self.mesh)
        #: seconds of each phase of the set-up, for the log
        self.phases = {}
        t = time.time()
        self.weights = weights.make(self.sizes, self.config["dtype"], seed,
                                    self._shardings)
        self.phases["weights"] = time.time() - t
        t = time.time()
        self.sut = system.System(self.config, self.traffic, self.weights,
                                 mesh=self.mesh)
        self.phases["compile"] = time.time() - t
        t = time.time()
        self.sut.warm_up(self.sizes["vocab"])
        self.phases["warm_up"] = time.time() - t

    def reseed(self, seed: int) -> None:
        """Serve the next window with the weights of `seed`."""
        from bench import weights
        self.weights = None
        self.sut.engine.params = None
        self.weights = weights.make(self.sizes, self.config["dtype"], seed,
                                    self._shardings)
        self.sut.engine.params = self.weights

    @property
    def warm_s(self) -> float:
        return float(self.traffic["traffic"].get("warm_s", 0.0))

    def stream(self, seed: int, seconds: float):
        """The traffic of one serve call: the warm-in and the window."""
        from bench import workloads
        spec = workloads.TrafficSpec.from_json(self.traffic["traffic"])
        return workloads.generate(spec, seed, self.warm_s + seconds,
                                  self.sizes["vocab"],
                                  limit=self.sut.max_tokens)

    def window(self, stream, seed: int, seconds: float,
               tracer: Optional[Tracer] = None):
        """Serve `stream`: the traffic's warm-in, then one window of
        `seconds`. Returns `system.Served`."""
        from bench import weights
        sut = self.sut
        sut.recorder.on_chunk = tracer.on_chunk if tracer else None
        if tracer is not None:
            tracer.arm(time.time() + self.warm_s
                       + max(0.0, seconds - TRACE_SECONDS))
        served = sut.serve(stream, seconds, weights.key_seed(seed, 3),
                           warm_s=self.warm_s)
        sut.recorder.on_chunk = None
        return served

    def context(self, served, seconds: float, setup_s: float = 0.0,
                t_trace: Optional[float] = None,
                trace=None) -> "RunContext":
        """What the metric modules read of one window."""
        return RunContext(
            cell=self.name, config=self.config, traffic=self.traffic,
            dims=self.dims, peaks=self.peaks, chips=self.cell["chips"],
            seconds=seconds, setup_s=setup_s,
            window_s=served.t1 - served.t0, t0=served.t0, t1=served.t1,
            requests=served.requests, step_stats=served.step_stats,
            records=served.records,
            traced_records=[r for r in served.records
                            if t_trace is not None and r.t0 >= t_trace],
            trace=trace)

    def check(self, served, seed: int, lowp: Optional[str] = None) -> Dict:
        """The reference's reading of the served tokens (and, with
        `lowp`, the control's)."""
        from bench import check
        finished = [check.Sample(r["prompt"],
                                 np.asarray(r["output"], np.int32))
                    for r in served.requests
                    if r["status"] in SERVED and r["output"]]
        sample = check.draw(finished, seed, CHECK_TOKENS, CHECK_REQUESTS)
        return check.gaps(self.config["reference"], self.sizes,
                          self.weights, sample, check_seq_len(self.traffic),
                          self.traffic["traffic"]["output"]["hi"],
                          lowp=lowp)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: str = ROOT, log=print,
             config: Optional[Dict] = None,
             traffic: Optional[Dict] = None,
             peaks: Optional[Dict] = None) -> Dict:
    """One run of the cell `name`: the result line as a dict."""
    import jax
    from bench import trace_reduce

    s = Session(name, seed, root=root, config=config, traffic=traffic,
                peaks=peaks)
    stream = s.stream(seed, seconds)
    log(f"before the serve {time.time() - t_process:.3f} s ("
        + ", ".join(f"{k} {v:.3f}" for k, v in s.phases.items())
        + f"); {stream.n} requests; chunk needs {s.sut.chunk_bytes()} B "
        "per chip")

    tracer = Tracer(jax) if trace else None
    served = s.window(stream, seed, seconds, tracer)
    trace_path = tracer.stop() if tracer is not None else None
    # set-up ends where the window opens, after the traffic's warm-in
    setup_s = served.t0 - t_process
    log(f"set-up {setup_s:.3f} s; window {served.t1 - served.t0:.3f} s, "
        f"the serve call {time.time() - served.start:.3f} s")
    device = _device_summary(jax, s.devices)
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      s.sut.chunk_bytes())
    s.sut.close()
    gc.collect()

    t = time.time()
    got = s.check(served, seed)
    log(f"check: {got['requests']} requests, {got['tokens']} served "
        f"tokens, {time.time() - t:.1f} s")
    s.weights = None

    summary = None
    if trace_path is not None:
        t = time.time()
        summary = trace_reduce.reduce_trace(trace_path, KERNELS,
                                            span_prefix="bench.")
        log(f"trace: {os.path.getsize(trace_path)} B reduced in "
            f"{time.time() - t:.1f} s")
    if tracer is not None:
        tracer.close()
    ctx = s.context(served, seconds, setup_s,
                    tracer.started_at if tracer is not None else None,
                    summary)

    metrics = {}
    for m in cell_metrics(s.bench, name, trace):
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    limit = s.config["check"]["logit_gap_limit"]
    gap = got["logit_gap"]
    statuses = [r["status"] for r in served.requests]
    result = {
        "correct": bool(gap is not None and gap <= limit),
        "attempted": len(statuses),
        "failed": sum(st in ("failed", "rejected") for st in statuses),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None and summary.devices:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = {"logit_gap": {"value": gap, "limit": limit}}
    return result
