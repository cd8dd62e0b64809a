"""The system under test: the program's model and `ServingEngine`, built
from a configuration file and a traffic file.

The only module of the benchmark that imports the program. It takes
the program's model, engine, sharding rules and requests, and reads
back the counters that the engine's serve chunks return. The engine's
compiled chunk is wrapped by `ChunkRecorder`, which keeps each chunk's
outputs (tokens emitted, first tokens, prompt tokens consumed per step
and lane) and each lane's cache length before the chunk, and marks the
chunk on the profiler's host timeline.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import configs  # noqa: E402
from repro.core.tiers import spec_for_device  # noqa: E402
from repro.launch import shardings as shd  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import EngineConfig, ServingEngine  # noqa: E402
from repro.serving.scheduler import Request  # noqa: E402

from bench.work import ChunkRecord  # noqa: E402

#: host spans the harness writes into the profiler's trace
SPAN = "bench."


def model_config(config: Dict):
    """The program's `ModelConfig` for a configuration file: the named
    config with the file's sizes and dtype put in."""
    base = configs.get(config["program_config"])
    fields = {f.name for f in dataclasses.fields(base)}
    unknown = set(config["sizes"]) - fields
    if unknown:
        raise ValueError(f"sizes not in ModelConfig: {sorted(unknown)}")
    dt = getattr(jnp, config["dtype"])
    return dataclasses.replace(base, **config["sizes"], dtype=dt,
                               param_dtype=dt)


def make_mesh(config: Dict):
    mesh = config.get("mesh")
    if not mesh:
        return None
    return make_test_mesh(data=mesh.get("data", 1), model=mesh["model"])


def weight_shardings(model: Model, mesh):
    """Where the serving engine keeps each weight on `mesh` (None:
    one device)."""
    if mesh is None:
        return None
    return shd.param_shardings(model.logical_axes(), model.abstract_params(),
                               mesh, "serve")


#: output positions of the chunk's lane counters, counted from the end:
#: both modes return (..., emitted, first, failed, prefill, stats)
EMITTED, FIRST, PREFILL = -5, -4, -2


class ChunkRecorder:
    """Stands in for the engine's serve-chunk callable: runs the
    compiled chunk and keeps a `ChunkRecord` per call. `on_chunk` is
    called before each chunk with the host time (the harness starts
    the profiler from there)."""

    def __init__(self, fn: Callable):
        self.fn = fn
        self.records: List[ChunkRecord] = []
        self.on_chunk: Optional[Callable[[float], None]] = None

    def __call__(self, *args):
        if self.on_chunk is not None:
            self.on_chunk(time.time())
        with jax.profiler.TraceAnnotation(SPAN + "read_lengths"):
            length0 = np.asarray(args[1].length)       # the cache state
        t0 = time.time()
        with jax.profiler.TraceAnnotation(SPAN + "serve_chunk"):
            out = self.fn(*args)
            emitted, first, prefill = (
                np.asarray(out[i]) for i in (EMITTED, FIRST, PREFILL))
        self.records.append(ChunkRecord(
            length0=length0, emitted=emitted, first=first, prefill=prefill,
            t0=t0, t1=time.time()))
        return out


def _spanned(fn: Callable, name: str) -> Callable:
    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(SPAN + name):
            return fn(*args, **kwargs)
    return call


@dataclasses.dataclass
class Served:
    """One serve call: its requests as plain records and its chunk
    records, and the window (`t0` to `t1`, host `time.time()`), which
    opens `warm_s` after the call's `start`, with the engine's
    telemetry (h_read, e_read, m_in, m_out bytes) of the window's
    decode steps."""

    requests: List[Dict]
    step_stats: np.ndarray
    records: List[ChunkRecord]
    start: float
    t0: float
    t1: float


class System:
    """The engine for one cell: built, compiled and warmed by
    `__init__`; `serve` runs one window."""

    def __init__(self, config: Dict, traffic: Dict, weights, mesh=None):
        self.model = Model(model_config(config))
        eng_cfg = dict(traffic["engine"])
        self.lanes = eng_cfg.pop("lanes")
        dev = jax.devices()[0]
        ecfg = EngineConfig(spec=spec_for_device(dev), eos_id=None, **eng_cfg)
        self.engine = ServingEngine(self.model, weights, ecfg, mesh=mesh)
        eng = self.engine
        shapes = eng.serve_chunk_shapes(self.lanes)
        self.compiled = eng._serve_jit.lower(*shapes).compile()
        self.memory = self.compiled.memory_analysis()
        self.recorder = ChunkRecorder(self.compiled)
        eng._serve_jit = self.recorder
        eng._release_jit = _spanned(eng._release_jit, "release_lanes")
        self.geo = eng.geo
        self.max_tokens = self.geo.max_tokens

    def chunk_bytes(self) -> int:
        """Device bytes the serve chunk needs at once, per chip, by the
        compiler's count: arguments, temporaries and outputs that do
        not reuse an argument's buffer."""
        m = self.memory
        return int(m.argument_size_in_bytes + m.temp_size_in_bytes
                   + m.output_size_in_bytes - m.alias_size_in_bytes)

    def warm_up(self, vocab: int) -> None:
        """Serve one short request per lane: runs the compiled chunk,
        the lane release and every small program of the serve loop, so
        that none compiles in the window."""
        c = self.engine.cfg.prefill_chunk
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(0, vocab, c + 1),
                        max_new_tokens=2) for i in range(self.lanes)]
        self.engine.serve(reqs, num_slots=self.lanes, seed=0)
        self.engine.state = None
        self.recorder.records = []

    def serve(self, stream, seconds: float, seed: int,
              warm_s: float = 0.0) -> Served:
        """One serve call: `warm_s` of traffic that fills the lanes,
        then the window of `seconds`. Every request's deadline falls at
        the window's end, where the engine reaps what is still queued
        or running at its next chunk boundary. The window opens and
        closes inside chunks: a chunk's steps count by their stamps."""
        end = warm_s + seconds
        reqs = []
        for i in range(stream.n):
            a = float(stream.arrival_s[i])
            reqs.append(Request(rid=i, prompt=stream.prompts[i],
                                max_new_tokens=int(stream.max_new[i]),
                                arrival_s=a,
                                deadline_s=max(end - a, 1e-3)))
        self.recorder.records = []
        start = time.time()
        report = self.engine.serve(reqs, num_slots=self.lanes, seed=seed)
        out = []
        for r in report.completed + report.rejected:
            out.append({
                "rid": r.rid, "status": r.status,
                "prompt": np.asarray(r.prompt, np.int32),
                "output": list(r.output), "due": start + r.arrival_s,
                "submitted": r.submitted_at, "admitted": r.admitted_at,
                "first": r.first_token_at, "finished": r.finished_at,
                "queue_wait": r.queue_wait_s})
        records = self.recorder.records
        t0, t1 = start + warm_s, start + end
        # the engine prices a step where some lane decoded, in order
        priced = [r.in_window(t0, t1)[r.emitted.max(axis=1) >= 0]
                  for r in records]
        keep = np.concatenate(priced) if priced else np.zeros(0, bool)
        stats = np.asarray([(s.h_read, s.e_read, s.m_in, s.m_out)
                            for s in self.engine.stats],
                           np.float64).reshape(-1, 4)
        if len(stats) != len(keep):
            raise RuntimeError(f"{len(stats)} priced steps for {len(keep)}"
                               " decode steps in the chunk records")
        return Served(requests=sorted(out, key=lambda d: d["rid"]),
                      step_stats=stats[keep], records=records,
                      start=start, t0=t0, t1=t1)

    def close(self) -> None:
        """Free the engine's cache and compiled chunk."""
        eng = self.engine
        eng.state = None
        eng._serve_jit = None
        self.recorder.fn = None
        self.compiled = None
        eng.params = None
