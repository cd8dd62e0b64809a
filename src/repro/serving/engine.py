"""Serving engine: the paper's dynamic KV placement as a live feature.

The entire decode step runs as ONE jitted, statically-shaped program on
device (see `repro.serving.control` and EXPERIMENTS.md §Fused-engine):

  1. control plane (jit): write-slot selection, Quest-style top-k page
     masking, and the importance-EMA migration planner, vectorized over
     [L, B] — no Python loops, no host round-trips.
  2. data plane (jit): `decode_step` over the two-tier paged cache;
     per-page attention-mass importance stats fall out of the attention
     kernel for free.
  3. data plane (jit): `apply_migrations` executes a FIXED-capacity
     `MigrationPlan` (capacity depends only on geometry and
     `migration_budget_frac`), so it compiles exactly once.
  4. telemetry: the step emits a tiny [4] int32 vector (resident HBM /
     host pages, promotes, demotes); the host prices it with the
     paper's Eq.(1)-(5) under a `MemorySystemSpec`.

Drive modes share the identical step function, so their logits are
bitwise identical and their byte accounting matches exactly:

  eager  `step(token)`         — one jitted call + host readback per
                                 token (the debugging / reference path)
  fused  `run(tokens)` /       — `lax.scan` over chunks of
         `generate(token, n)`    `telemetry_stride` steps with the
                                 cache donated; the host reads back one
                                 [stride, 4] stats array per chunk.
  serve  `serve(requests)`     — the headline API: continuous batching
                                 over the same fused chunks, where each
                                 step is a MIXED prefill+decode step:
                                 decoding lanes emit one sampled token
                                 (temperature/top-k/top-p, greedy at
                                 temperature 0) while prefilling lanes
                                 consume a `prefill_chunk`-token slice
                                 of their prompt, writing pages
                                 directly into their lane of the
                                 shared cache at an offset. The first
                                 output token is sampled ON DEVICE at
                                 the step prefill crosses prompt_len
                                 (TTFT is a device event); admission,
                                 completion and page reclaim happen at
                                 chunk boundaries without retracing —
                                 ONE executable for the whole stream,
                                 whatever the prompt-length mix.
                                 Returns a `ServeReport` (completed
                                 requests + TTFT/TPOT percentiles).

Engine policies are a pluggable PLANE (`repro.serving.policies`): every
registered `DevicePolicy` — static, importance, recency, cost_aware,
quest — plans through the same fixed-capacity `control.plan_by_score`
core and threads its own (statically shaped) state through the scan,
so each policy runs the full serve stream on ONE compiled executable.
`EngineConfig.trace_telemetry` additionally captures per-step page
accesses + placements — lane 0 for the single-stream modes, every lane
(plus lane->request bindings) for `serve` — which
`repro.serving.trace_bridge` converts into simulator traces (stitched
per request for serve streams) and scores against the paper's SA upper
bound.

`EngineConfig.overlap_migrations` pipelines the migration plane inside
the serve scan: step N commits the (revalidated, fault-throttled) plan
staged at step N-1 concurrently with decode compute, and plans for
step N+1 off this step's read set — a double-buffered plan/commit
split with one-step-ahead KV prefetch (EXPERIMENTS.md
§Async-migration). Decode semantics are placement-invariant, so the
pipeline changes WHEN pages move, never what attention computes.

Scaling out: `ServingEngine(model, params, cfg, mesh=...)` runs the
identical serve loop across a jax device mesh — cache pools, migration
plans, policy state, and the fault channel become mesh-sharded pytrees
under the sharding rules in `repro.launch.shardings`, with one
executable and zero retraces per (policy, mesh). See the `serve`
docstring and EXPERIMENTS.md §Mesh-sharding.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency_model import StepTraffic, step_latency
from repro.core.tiers import MemorySystemSpec, TPU_V5E
from repro.kernels import ops
from repro.kvcache.migrate import MigrationPlan, apply_migrations
from repro.kvcache.paged import (
    PagedKVCache, abstract_cache, init_cache,
)
from repro.models.model import Model
from repro.serving import control
from repro.serving.faults import FaultPlane, NO_FAULT_CAP, throttle_plan
from repro.serving.policies import make_policy, policy_names
from repro.serving.sampling import (
    SamplingConfig, lane_key, make_sampler, split_lanes,
)
from repro.serving.scheduler import (
    ContinuousBatcher, Request, RequestError,
)
from repro.serving.slo import SLOPolicy


@dataclasses.dataclass
class EngineConfig:
    """Static engine configuration, baked into the jitted step
    functions at build time (changing any field recompiles once; no
    field may change mid-stream). Selects the cache geometry split
    (`max_context`, `hbm_fraction`), the placement policy and its
    knobs, attention sparsity, the fused-scan stride, chunked-prefill
    budgets, EOS, and trace capture."""

    max_context: int = 512
    hbm_fraction: float = 0.25
    policy: str = "importance"
    #: fraction of pages bypassed at attention (0 = dense attention)
    attention_sparsity: float = 0.0
    #: migration budget per step, as a fraction of HBM pages
    migration_budget_frac: float = 0.1
    promote_thresh: float = 0.02     # attention-mass EMA threshold
    spec: MemorySystemSpec = TPU_V5E
    #: fused-mode scan length: decode steps run on device between
    #: telemetry readbacks (1 = eager cadence, larger = fewer syncs)
    telemetry_stride: int = 32
    #: chunked-prefill token budget: prompt tokens each PREFILLING lane
    #: consumes per mixed serve step. A static shape — lane index and
    #: prompt offset are data — so one serve-chunk executable covers
    #: every prompt length; chunking is bitwise-invisible (any budget
    #: reproduces the whole-prompt prefill exactly).
    prefill_chunk: int = 32
    #: per-BATCH prefill token budget for mixed serve steps (None =
    #: uncapped). A token bucket refilled `prefill_budget` tokens per
    #: step: the prefill plane runs only when the accrued budget covers
    #: the step's total prompt-slice demand across lanes, so a heavy
    #: prefill wave dilutes over steps instead of taxing every decode
    #: step — decode TPOT under the wave improves, TTFT of the wave
    #: stretches. GREEDY streams are token-for-token unchanged
    #: (schedule only); sampled streams (temperature > 0) stay
    #: per-request reproducible but draw from a shifted point of the
    #: lane's key chain, since each lane's PRNG advances every step
    #: and the budget moves the prefill-to-decode crossing. Per-lane
    #: `prefill_chunk` still bounds each slice.
    prefill_budget: Optional[int] = None
    #: stop token for `serve` (None = budget-only completion)
    eos_id: Optional[int] = None
    #: capture per-step (page access, read-time placement) telemetry
    #: for the simulator bridge (`repro.serving.trace_bridge`).
    #: step/run/generate keep batch lane 0 (`trace_bridge.collect`);
    #: `serve` keeps EVERY lane plus its chunk's lane->request bindings
    #: so the bridge can stitch per-REQUEST traces across admission/
    #: reclaim boundaries (`trace_bridge.collect_serve`/`attribute`).
    #: Pure observation: tokens, StepStats, and executable counts are
    #: identical with capture on or off.
    trace_telemetry: bool = False
    #: policy fallback: after this many CONSECUTIVE chunk boundaries
    #: whose migration commits were fully dropped (a MigrationFault
    #: window forcing cap 0 at some step), `serve` degrades the policy
    #: to static behavior by uploading all-zero commit caps — same
    #: executable, migrations masked as data — and stamps a
    #: "policy_fallback" event. The fallback is sticky for the stream.
    fallback_commit_faults: int = 3
    #: policy fallback: degrade to static when a tier fault pushes the
    #: effective HBM:DRAM bandwidth ratio past this MULTIPLE of the
    #: base spec's ratio (relative, so GH200 ~9.8x and TPU v5e ~25.6x
    #: base ratios share one knob) — with the host tier that slow,
    #: migrating pages toward it can no longer pay back.
    fallback_tier_ratio: float = 8.0
    #: async-migration pipeline (EXPERIMENTS.md §Async-migration): the
    #: serve scan carries a STAGED MigrationPlan — step N commits the
    #: plan staged at step N-1 (revalidated against the commit-time
    #: owner maps, then throttled by the fault channel) concurrently
    #: with its decode compute, and plans for step N+1 off this step's
    #: read set (the one-step-ahead re-reference oracle; every policy
    #: grows `plan_ahead` eviction protection, active under sparse
    #: attention). False keeps the serial plan-then-commit step — the
    #: bitwise inline baseline. Decode semantics are
    #: placement-invariant (attention reads pages wherever they live),
    #: so the pipeline shifts placement timing — hit fractions,
    #: modeled latency, and at most the floating-point association of
    #: the per-tier LSE merge when interim placements differ.
    #: Serve-path only: step/run/generate always run inline.
    overlap_migrations: bool = False


@dataclasses.dataclass
class StepStats:
    """One decode step's modeled cost under the paper's Eq. (1)-(5):
    the latency and the byte volumes (HBM / host reads, migrations in /
    out) the engine's device telemetry priced for that step, plus the
    step's HBM hit rate (fraction of read bytes served from HBM) and
    its resident pages (pool slots holding a page, both tiers, summed
    over layers and lanes)."""

    modeled_latency_s: float
    h_read: float
    e_read: float
    m_in: float
    m_out: float
    hbm_hit_rate: float
    resident_pages: int


#: the host phases of `serve()`, each a profiler span `serve.<phase>`
PHASES = ("setup", "upload", "dispatch", "readback", "account", "reap",
          "release", "admit", "wait", "report")
#: the `jax.named_scope`s of the serve chunk's device work
SCOPES = ("decode", "lane_merge", "migrate", "prefill", "sample")


@dataclasses.dataclass
class ServeChunk:
    """One serve chunk as the host saw it (`ServeReport.chunks`). Every
    count is a sum over arrays the chunk boundary reads back anyway: no
    extra device output, no extra sync.

    Host `time.time()`: `t_dispatch` as the chunk is dispatched,
    `t_ready` once its outputs are read back, `stamps` [stride] the
    time each step is stamped with (a request's `first_token_at` and
    `finished_at` are entries of it). `phase_s` holds the seconds of
    each host phase (the `serve.<phase>` spans) since the previous
    chunk's readback: the boundary before this chunk (`setup` for the
    first) and the chunk's own dispatch and readback.

    `rids` [B] is each lane's request (-1: free). Per step [stride]:
    `decoding` counts the lanes that emitted a decode token,
    `prefilling` the lanes that consumed prompt tokens, and
    `prompt_tokens` the tokens they consumed. `admitted` counts the
    requests admitted since the previous chunk, `queue_depth` those
    queued at dispatch, and `released` the lanes released after it."""

    t_dispatch: float
    t_ready: float
    stamps: np.ndarray
    phase_s: Dict[str, float]
    rids: np.ndarray
    decoding: np.ndarray
    prefilling: np.ndarray
    prompt_tokens: np.ndarray
    admitted: int
    queue_depth: int
    released: int = 0


class _Phases:
    """The host phases of one `serve()` call. `to(name)` ends the
    running phase and starts `name` (one of `PHASES`; None ends the
    last), so the phases tile the call. Each runs under the profiler
    span `serve.<name>`, on the device trace's clock, and its seconds
    add to `tally[name]` until `take()` hands them to a chunk record."""

    def __init__(self):
        self.tally: Dict[str, float] = {}
        self._name: Optional[str] = None
        self._span = None
        self._t = 0.0

    def to(self, name: Optional[str]) -> None:
        if name == self._name:
            return
        if name is not None and name not in PHASES:
            raise ValueError(f"unknown serve phase {name!r}")
        now = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self.tally[self._name] = \
                self.tally.get(self._name, 0.0) + now - self._t
        self._name, self._t, self._span = name, now, None
        if name is not None:
            self._span = jax.profiler.TraceAnnotation("serve." + name)
            self._span.__enter__()

    def take(self) -> Dict[str, float]:
        """The seconds by phase since the last `take()`."""
        out, self.tally = self.tally, {}
        return out


def _scope(name: str):
    """The serve chunk's `jax.named_scope` `name` (one of `SCOPES`)."""
    if name not in SCOPES:
        raise ValueError(f"unknown serve scope {name!r}")
    return jax.named_scope(name)


@dataclasses.dataclass
class ServeReport:
    """`serve()`'s return value: the completed requests plus
    request-level latency percentiles (seconds) — TTFT measured from
    `submitted_at` to the boundary where the on-device first token is
    read back, TPOT as decode seconds per token after the first.
    Sequence-like over `completed`, so `for r in report` / `report[0]`
    / `len(report)` keep working at PR 2 call sites.

    `completed` holds every request that occupied a lane — terminal
    status "ok", or "failed"/"cancelled"/"timeout" when the engine
    quarantined or reaped it mid-flight; `rejected` holds requests
    refused before admission (invalid, infeasible, duplicate rid, or
    reaped while still queued), each with a typed `Request.error`.
    `statuses` maps every submitted rid to its terminal status — the
    stream NEVER raises on a per-request condition, so the mapping is
    exhaustive. `events` is the chronological degradation log (injected
    faults activating, pool resizes, policy fallback) a faulted stream
    accumulated — see `repro.serving.faults`.

    When the stream ran with `EngineConfig.trace_telemetry` and the
    bridge scored it (`trace_bridge.score_serve(..., report=...)`),
    `request_scores` maps each request id to its attributed placement
    scores (`hit_fraction`, `bound_fraction`, ...) and `headroom`
    carries the aggregate stream's live-vs-SA-bound summary. Both stay
    empty otherwise — scoring replays the SA oracle and is a
    deliberate post-pass, not part of the serve hot loop."""

    completed: List[Request]
    ttft: Dict[str, float] = dataclasses.field(default_factory=dict)
    tpot: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: TTFT decomposition percentiles: queue_wait / prefill / throttle
    #: (per request the three sum to TTFT — queue_wait is submit ->
    #: first chunk, prefill the seconds of steps that consumed prompt
    #: tokens, throttle the budget-starved + boundary-overhead rest)
    ttft_parts: Dict[str, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)
    #: EOS accounting: {"eos_id", "eos_stops", "budget_stops"} — how
    #: many "ok" requests stopped on the configured EOS id vs ran out
    #: their token budget (`Request.stop_reason` per request)
    eos: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: goodput-under-SLO row (stamped by `slo.score_goodput`; empty
    #: when the stream was not scored against an SLOPolicy)
    goodput: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: requests refused before admission (typed `Request.error` each)
    rejected: List[Request] = dataclasses.field(default_factory=list)
    #: chronological degradation events (fault activations, pool
    #: resizes, payback recalibrations, policy fallback)
    events: List[dict] = dataclasses.field(default_factory=list)
    #: rid -> per-request attribution scores (trace_bridge.score_serve)
    request_scores: Dict[int, Dict[str, float]] = \
        dataclasses.field(default_factory=dict)
    #: aggregate stream headroom (live vs SA/Belady/static totals)
    headroom: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: the stream's serve chunks in order, as the host saw them
    chunks: List[ServeChunk] = dataclasses.field(default_factory=list)

    @property
    def statuses(self) -> Dict[int, str]:
        """rid -> terminal status, exhaustive over every request that
        entered `serve` (completed and rejected alike)."""
        return {r.rid: r.status for r in self.completed + self.rejected}

    @staticmethod
    def build(completed: List[Request],
              rejected: Optional[List[Request]] = None,
              events: Optional[List[dict]] = None,
              eos_id: Optional[int] = None,
              chunks: Optional[List[ServeChunk]] = None) -> "ServeReport":
        """Assemble a report from terminal requests: TTFT/TPOT
        mean/p50/p95 from the completed requests' wall-clock stamps,
        the TTFT decomposition percentiles, and EOS-stop counts."""
        def pct(vals):
            if not vals:
                return {}
            v = np.asarray(vals, np.float64)
            return {"mean": float(v.mean()),
                    "p50": float(np.percentile(v, 50)),
                    "p95": float(np.percentile(v, 95))}

        ttfts = [r.first_token_at - r.submitted_at for r in completed
                 if r.first_token_at is not None]
        tpots = [(r.finished_at - r.first_token_at)
                 / (len(r.output) - 1)
                 for r in completed
                 if r.first_token_at is not None
                 and r.finished_at is not None and len(r.output) > 1]
        # decomposition percentiles over requests stamped both at
        # admission and at their first token
        attributed = [r for r in completed
                      if r.first_token_at is not None
                      and r.admitted_at is not None]
        parts = {
            "queue_wait": pct([r.queue_wait_s for r in attributed]),
            "prefill": pct([r.prefill_s for r in attributed]),
            "throttle": pct([r.throttle_s for r in attributed]),
        }
        eos = {
            "eos_id": eos_id,
            "eos_stops": sum(1 for r in completed
                             if r.stop_reason == "eos"),
            "budget_stops": sum(1 for r in completed
                                if r.stop_reason == "budget"),
        }
        return ServeReport(completed=list(completed), ttft=pct(ttfts),
                           tpot=pct(tpots), ttft_parts=parts, eos=eos,
                           rejected=list(rejected or []),
                           events=list(events or []),
                           chunks=list(chunks or []))

    def __iter__(self):
        return iter(self.completed)

    def __len__(self) -> int:
        return len(self.completed)

    def __getitem__(self, i):
        return self.completed[i]


def _get_cache(state) -> PagedKVCache:
    return state if isinstance(state, PagedKVCache) else state["kv"]


def _set_cache(state, cache):
    if isinstance(state, PagedKVCache):
        return cache
    return {**state, "kv": cache}


class ServingEngine:
    """The live serving engine over the two-tier paged KV cache.

    Owns the jitted fused step (control plane + decode + migration, see
    the module docstring) and exposes the drive modes: eager `step`,
    fused `run`/`generate`, and the continuous-batching `serve`. Device
    telemetry is priced per step into `self.stats` (`StepStats`,
    Eq. (1)-(5)); with `EngineConfig.trace_telemetry` the raw page
    access/placement stream is additionally kept for the simulator
    bridge (`repro.serving.trace_bridge`)."""

    def __init__(self, model: Model, params, cfg: EngineConfig,
                 mesh=None):
        if cfg.policy not in policy_names():
            raise ValueError(
                f"unknown EngineConfig.policy {cfg.policy!r}; registered "
                f"device policies: {', '.join(policy_names())}")
        if cfg.prefill_budget is not None and cfg.prefill_budget < 1:
            raise ValueError(
                f"EngineConfig.prefill_budget must be >= 1 tokens/step "
                f"or None (uncapped), got {cfg.prefill_budget}")
        if mesh is not None and "model" not in mesh.axis_names:
            raise ValueError(
                f"ServingEngine mesh needs a 'model' axis (and usually "
                f"'data'); got axes {mesh.axis_names}")
        self.model = model
        self.params = params
        self.cfg = cfg
        #: optional jax device mesh: `serve` then pins NamedShardings
        #: on the fused chunk — KV pools tensor-parallel over kv_heads
        #: or pages (`launch.shardings._kv_shard_axis`), lanes
        #: data-parallel over `batch_axes` — and places params / cache
        #: / policy state once per stream. None = single device, the
        #: exact pre-mesh behavior. A constructor argument, not an
        #: EngineConfig field: the compiled executables are keyed on
        #: it (`_ensure_step_fns`), but a Mesh is device state, not a
        #: serializable config value.
        self.mesh = mesh
        self.stats: List[StepStats] = []
        self._sampling = SamplingConfig()
        #: raw (stats, access, tier) chunks when cfg.trace_telemetry
        #: (consumed by repro.serving.trace_bridge.collect)
        self._trace_log: List[tuple] = []

    # ------------------------------------------------------------------ #
    def start(self, prompts: jax.Array, extra=None):
        """Prefill `prompts` [B, S] into a fresh cache and return the
        last-position logits; resets stats and any captured trace.
        The single-stream entry point — `serve` manages its own cache
        and admission, `start` is for step/run/generate driving."""
        geo = self.model.cache_geometry(
            prompts.shape[0], self.cfg.max_context,
            hbm_fraction=self.cfg.hbm_fraction)
        self.geo = geo
        logits, state = self.model.prefill(self.params, prompts, geo,
                                           extra=extra)
        self.state = state
        self._ensure_step_fns()
        self._pstate = self._policy.init_state(geo)
        self._trace_log = []
        self._trace_prompt_len = int(prompts.shape[1])
        return logits

    @property
    def _cache(self) -> PagedKVCache:
        return _get_cache(self.state)

    # ------------------------------------------------------------------ #
    # the fused step: control plane + data plane + migration, all jit
    # ------------------------------------------------------------------ #
    def _ensure_step_fns(self):
        """(Re)build the jitted step functions only when the cache
        geometry, sampling config, or engine config changed, so repeated
        `serve`/`start` calls over the same shapes reuse the compiled
        executables (cfg is part of the key because the step closures
        bake in policy/threshold/stride/eos; the mesh because the serve
        jit pins its shardings)."""
        key = (self.geo, self._sampling, dataclasses.astuple(self.cfg),
               self.mesh)
        if getattr(self, "_fns_key", None) != key:
            self._build_step_fns()
            self._fns_key = key

    def _build_step_fns(self):
        cfg, model, geo = self.cfg, self.model, self.geo
        overlap = cfg.overlap_migrations
        sparsity = cfg.attention_sparsity
        fam = model.cfg.family
        has_cache = fam in ("dense", "vlm", "moe", "encdec") or (
            fam in ("ssm", "hybrid")
            and bool(model.cfg.attention_layer_ids()))
        masked = sparsity > 0 and has_cache
        policy = make_policy(cfg.policy, cfg=cfg, geo=geo)
        self._policy = policy
        budget = control.migration_budget(geo, cfg.migration_budget_frac)
        capture = cfg.trace_telemetry
        eos = cfg.eos_id
        sampler = make_sampler(self._sampling)
        self._sampler = sampler

        def step_fn(params, state, pstate, token, active=None,
                    mig_cap=None):
            cache = _get_cache(state)
            kwargs = {"write_slot": control.choose_write_slot(cache,
                                                              active)}
            mask = None
            if masked:
                mask = control.quest_page_mask(cache, sparsity)
                kwargs["logical_page_mask"] = mask
            # the read set this step's attention streams: the Quest
            # mask (already alive-gated), or every pre-decode page —
            # handed to the policy (so access-history policies track
            # the true stream) and to the telemetry capture
            read = mask if mask is not None else cache.page_table >= 0
            with _scope("decode"):
                logits, state = model.decode_step(params, state, token,
                                                  **kwargs)
            if active is not None:
                # per-slot masking: inactive lanes keep their pre-step
                # cache verbatim (NO_WRITE dropped their token write;
                # the merge undoes their length bump and importance EMA)
                with _scope("lane_merge"):
                    state = _set_cache(state, control.lane_merge(
                        cache, _get_cache(state), active))
            cache = _get_cache(state)
            # read traffic is counted on post-decode, pre-migration
            # residency (the step's attention read the old placement)
            occ = control.occupancy(cache)
            with _scope("migrate"):
                plan, pstate, (n_pro, n_dem) = policy.plan(
                    cache, pstate, active, budget, read_mask=read)
                if mig_cap is not None:
                    # migration-fault channel (serve only): commit at most
                    # `mig_cap` promote rows this step — cap is traced DATA
                    # (NO_FAULT_CAP = identity), so the clean and faulted
                    # streams share one executable. Telemetry counts the
                    # COMMITTED moves, so pricing and the bridge's scores
                    # see the placement that actually happened.
                    plan = throttle_plan(plan, mig_cap)
                    n_pro, n_dem = plan.row_counts()
            moves = jnp.stack([n_pro, n_dem]).astype(jnp.int32)
            base = jnp.concatenate([occ, moves])
            if capture:
                # full-batch read set + read-time placement (post-decode
                # so the step's fresh page is included, pre-migration).
                # `_record` keeps lane 0 for the generate bridge; the
                # serve capture keeps every lane for per-request
                # attribution (trace_bridge.collect_serve).
                stats = (base, read, control.page_tiers(cache))
            else:
                stats = (base,)
            with _scope("migrate"):
                state = _set_cache(state, apply_migrations(cache, plan))
            return logits, state, pstate, stats

        def chunk_fn(params, state, pstate, tokens):
            """Teacher-forced fused decode over tokens [n, B]."""
            def body(carry, tok):
                st, ps = carry
                logits, st, ps, stats = step_fn(params, st, ps, tok)
                return (st, ps), (logits, stats)
            (state, pstate), (logits, stats) = jax.lax.scan(
                body, (state, pstate), tokens)
            return state, pstate, logits, stats

        def gen_fn(params, state, pstate, token, n):
            """Greedy self-feeding fused decode for n steps."""
            def body(carry, _):
                st, ps, tok = carry
                logits, st, ps, stats = step_fn(params, st, ps, tok)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (st, ps, nxt), (nxt, stats)
            (state, pstate, token), (toks, stats) = jax.lax.scan(
                body, (state, pstate, token), None, length=n)
            return state, pstate, token, toks, stats

        def step_overlap_fn(params, state, pstate, staged, token, active,
                            mig_cap):
            """Overlap-mode serve step: the double-buffered plan/commit
            split. Three stages, all in one traced program:

              1. decode against the PRE-commit placement (the commit
                 lands "concurrently" with this compute — on real
                 hardware the staged cross-pool scatter is an async DMA
                 the forward hides; in the traced program it is
                 sequenced after the decode so the step's reads see the
                 old placement, the bitwise expression of overlap);
              2. COMMIT the plan staged one step ago: hazard-revalidated
                 against the commit-time owner maps
                 (`control.revalidate_plan` — a page never commits into
                 a slot the in-flight step just allocated) and throttled
                 by the fault channel (the chaos caps govern what
                 COMMITS, exactly as inline — telemetry counts committed
                 moves);
              3. PLAN for the step after next on the post-commit
                 placement, with this step's read set as the
                 one-step-ahead re-reference oracle (`plan_ahead`
                 policies protect it from eviction). The fresh plan is
                 the new staged carry.
            """
            cache = _get_cache(state)
            kwargs = {"write_slot": control.choose_write_slot(cache,
                                                              active)}
            mask = None
            if masked:
                mask = control.quest_page_mask(cache, sparsity)
                kwargs["logical_page_mask"] = mask
            read = mask if mask is not None else cache.page_table >= 0
            with _scope("decode"):
                logits, state = model.decode_step(params, state, token,
                                                  **kwargs)
            with _scope("lane_merge"):
                state = _set_cache(state, control.lane_merge(
                    cache, _get_cache(state), active))
            cache = _get_cache(state)
            # occupancy + read-time placement are PRE-commit: this
            # step's attention read the old placement
            occ = control.occupancy(cache)
            tiers = control.page_tiers(cache) if capture else None
            with _scope("migrate"):
                commit = control.revalidate_plan(staged, cache)
                commit = throttle_plan(commit, mig_cap)
                n_pro, n_dem = commit.row_counts()
                cache = apply_migrations(cache, commit)
                state = _set_cache(state, cache)
                staged, pstate, _ = policy.plan(cache, pstate, active,
                                                budget, read_mask=read)
            moves = jnp.stack([n_pro, n_dem]).astype(jnp.int32)
            base = jnp.concatenate([occ, moves])
            stats = (base, read, tiers) if capture else (base,)
            return logits, state, pstate, staged, stats

        serveable = fam in ("dense", "moe")
        if serveable:
            C = max(1, cfg.prefill_chunk)
            S_cap = geo.max_tokens
            B = geo.batch
            Pb = cfg.prefill_budget
            use_budget = Pb is not None
            pf_logits_sds, _ = jax.eval_shape(
                model.prefill_chunk, self.params, abstract_cache(geo),
                jax.ShapeDtypeStruct((B, C), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32))

        def _serve_chunk_impl(params, state, pstate, staged, token, active,
                              remaining, keys, prefilled, prompt_len,
                              prompt_buf, credits, mig_caps, poison):
            """One fused chunk of MIXED prefill+decode steps.

            Carries per-slot (token, active, remaining budget, PRNG key,
            prompt progress) through `lax.scan`; per step the lane-mode
            split (`control.lane_modes`) is derived on device, decoding
            lanes run the decode plane (emitting into `emitted`, -1
            elsewhere) and prefilling lanes consume a C-token prompt
            slice (`model.prefill_chunk` — skipped via `lax.cond` when
            no lane is prefilling). The step where a lane's prefill
            crosses prompt_len samples its FIRST token from the last
            prompt position's logits (reported via `first`, not
            `emitted`, so telemetry still prices decode steps only) and
            the lane starts decoding the next step — all without host
            involvement. Completion (EOS / budget, including instant
            budget-1/EOS at the crossing) flips the lane's active bit
            on device; the host reclaims and re-admits at the chunk
            boundary.

            Fault channel (always compiled in — values, never shapes):
            `mig_caps` [stride] int32 caps each step's migration
            commits (`NO_FAULT_CAP` = untouched) and `poison`
            [stride, B] bool overwrites a lane's logits with NaN. The
            non-finite sampling guard is ALWAYS on, injected or not: a
            lane whose logits go NaN/Inf emits nothing that step, flips
            inactive, and is flagged in the `failed` output so the host
            completes it with status "failed" — every other lane's
            tokens are bitwise what they are in a clean run.

            Overlap mode threads one more carry leaf: the STAGED
            `MigrationPlan` — step N's decode plane commits the plan
            staged at N-1 and stages a fresh one (`step_overlap_fn`);
            pure-prefill steps pass it through untouched (the next
            decode's revalidation catches any prefill-allocated slot
            it names).
            """
            def body(carry, xs):
                if overlap:
                    st, ps, stg, tok, act, rem, ks, prog, cred = carry
                else:
                    st, ps, tok, act, rem, ks, prog, cred = carry
                    stg = None
                cap, poi = xs
                pf, dec = control.lane_modes(act, prog, prompt_len)

                # decode plane: skipped (lax.cond) on pure-prefill
                # steps — step_fn with dec all-False is a bitwise
                # no-op on the cache (every lane writes NO_WRITE and
                # lane_merge keeps every length and importance, the
                # planner plans nothing) and its stats row is
                # filtered at the boundary, so skipping it only saves
                # the dead forward
                def run_dec(args):
                    if overlap:
                        return step_overlap_fn(params, args[0], args[1],
                                               args[2], args[3], dec, cap)
                    return step_fn(params, args[0], args[1], args[2], dec,
                                   mig_cap=cap)

                def skip_dec(args):
                    c = _get_cache(args[0])
                    occ = control.occupancy(c)
                    vocab = pf_logits_sds.shape[-1]
                    base = jnp.concatenate([occ,
                                            jnp.zeros((2,), jnp.int32)])
                    if capture:
                        # pure-prefill step: no decode reads. The tier
                        # snapshot keeps the ys pytree static; the
                        # bridge drops these rows (no lane emitted).
                        nostats = (base,
                                   jnp.zeros(c.page_table.shape, bool),
                                   control.page_tiers(c))
                    else:
                        nostats = (base,)
                    zeros = jnp.zeros((B, vocab), pf_logits_sds.dtype)
                    if overlap:
                        # no decode, no commit: the staged plan waits
                        return (zeros, args[0], args[1], args[2],
                                nostats)
                    return (zeros, args[0], args[1], nostats)

                if overlap:
                    logits, st, ps, stg, stats = jax.lax.cond(
                        dec.any(), run_dec, skip_dec, (st, ps, stg, tok))
                else:
                    logits, st, ps, stats = jax.lax.cond(
                        dec.any(), run_dec, skip_dec, (st, ps, tok))
                if capture:
                    # decode-plane attribution only: a lane's reads
                    # count while it DECODES — prefilling lanes' pages
                    # are write traffic, not part of the access model
                    stats = (stats[0], stats[1] & dec[None, :, None],
                             stats[2])
                # poison injection + non-finite sampling guard. The
                # injected NaN and a genuinely non-finite model output
                # take the same quarantine path: the lane emits nothing
                # this step, keeps its budget, and flips inactive.
                nanv = jnp.asarray(jnp.nan, logits.dtype)
                logits = jnp.where((dec & poi)[:, None], nanv, logits)
                bad = dec & ~jnp.isfinite(logits).all(axis=-1)
                dec_ok = dec & ~bad
                with _scope("sample"):
                    ks, sub = split_lanes(ks)
                    nxt = sampler(logits, sub)
                rem = rem - dec_ok.astype(rem.dtype)
                fin = dec_ok & (rem <= 0)
                if eos is not None:
                    fin = fin | (dec_ok & (nxt == eos))
                emitted = jnp.where(dec_ok, nxt, -1)
                tok = jnp.where(dec_ok, nxt, tok)
                act = act & ~fin & ~bad

                # prefill plane: a C-token slice per prefilling lane,
                # written straight into its pages at offset `prog`
                n_val = jnp.where(pf, jnp.clip(prompt_len - prog, 0, C),
                                  0).astype(jnp.int32)
                if use_budget:
                    # per-batch token bucket: accrue Pb tokens/step
                    # (capped at one full step's demand) and run the
                    # prefill plane only when the bucket covers the
                    # step's TOTAL demand — heavy prefill waves dilute
                    # over steps instead of taxing every decode step
                    want_tot = n_val.sum()
                    cred = jnp.minimum(cred + jnp.int32(Pb),
                                       jnp.int32(B * C))
                    run_now = cred >= want_tot
                    n_val = jnp.where(run_now, n_val, 0)
                    cred = cred - jnp.where(run_now, want_tot, 0)
                idx = jnp.clip(prog[:, None] + jnp.arange(C), 0,
                               S_cap - 1)
                sl_toks = jnp.take_along_axis(prompt_buf, idx, axis=1)
                cache = _get_cache(st)

                def run_pf(args):
                    c, t, s, n = args
                    with _scope("prefill"):
                        return model.prefill_chunk(params, c, t, s, n)

                def skip_pf(args):
                    return (jnp.zeros(pf_logits_sds.shape,
                                      pf_logits_sds.dtype), args[0])

                # (n_val > 0).any() == pf.any() when unbudgeted (a
                # prefilling lane always wants >= 1 token); under a
                # budget it additionally skips bucket-starved steps
                logits_c, cache = jax.lax.cond(
                    (n_val > 0).any(), run_pf, skip_pf,
                    (cache, sl_toks, prog, n_val))
                st = _set_cache(st, cache)
                prog = prog + n_val
                crossed = pf & (prog >= prompt_len)
                last = jnp.clip(n_val - 1, 0, C - 1)
                logits1 = jnp.take_along_axis(
                    logits_c, last[:, None, None], axis=1)[:, 0]
                # the same poison + guard protects the crossing sample:
                # a lane poisoned (or non-finite) at its first token
                # fails before emitting anything
                nanv1 = jnp.asarray(jnp.nan, logits1.dtype)
                logits1 = jnp.where((pf & poi)[:, None], nanv1, logits1)
                bad0 = crossed & ~jnp.isfinite(logits1).all(axis=-1)
                crossed = crossed & ~bad0
                with _scope("sample"):
                    tok0 = sampler(logits1, sub)
                first = jnp.where(crossed, tok0, -1)
                tok = jnp.where(crossed, tok0, tok)
                rem = rem - crossed.astype(rem.dtype)
                fin0 = crossed & (rem <= 0)
                if eos is not None:
                    fin0 = fin0 | (crossed & (tok0 == eos))
                act = act & ~fin0 & ~bad0
                if overlap:
                    out_carry = (st, ps, stg, tok, act, rem, ks, prog,
                                 cred)
                else:
                    out_carry = (st, ps, tok, act, rem, ks, prog, cred)
                # n_val is the step's ACTUAL prompt consumption per
                # lane (0 on budget-starved steps) — the host's TTFT
                # decomposition splits a prefilling lane's chunk time
                # into prefill vs throttle off exactly this readback
                return out_carry, (emitted, first, bad | bad0, n_val,
                                   stats)

            if overlap:
                carry = (state, pstate, staged, token, active, remaining,
                         keys, prefilled, credits)
            else:
                carry = (state, pstate, token, active, remaining, keys,
                         prefilled, credits)
            carry, (emitted, first, failed, pf_tok, stats) = jax.lax.scan(
                body, carry, (mig_caps, poison))
            if overlap:
                (state, pstate, staged, token, active, remaining, keys,
                 prefilled, credits) = carry
                return (state, pstate, staged, token, active, remaining,
                        keys, prefilled, credits, emitted, first, failed,
                        pf_tok, stats)
            (state, pstate, token, active, remaining, keys, prefilled,
             credits) = carry
            return (state, pstate, token, active, remaining, keys,
                    prefilled, credits, emitted, first, failed, pf_tok,
                    stats)

        if overlap:
            def serve_chunk_fn(params, state, pstate, staged, token,
                               active, remaining, keys, prefilled,
                               prompt_len, prompt_buf, credits, stale,
                               mig_caps, poison):
                # boundary hygiene (overlap only): lanes the host
                # released or (re)bound since the plan was staged carry
                # rows revalidation cannot catch — static placement is
                # deterministic, so a re-admitted request can reproduce
                # the evicted one's exact (slot, logical) pairs. Mask
                # them out before the chunk runs.
                with _scope("migrate"):
                    staged = control.mask_plan_lanes(staged, stale)
                return _serve_chunk_impl(
                    params, state, pstate, staged, token, active,
                    remaining, keys, prefilled, prompt_len, prompt_buf,
                    credits, mig_caps, poison)
        else:
            def serve_chunk_fn(params, state, pstate, token, active,
                               remaining, keys, prefilled, prompt_len,
                               prompt_buf, credits, mig_caps, poison):
                return _serve_chunk_impl(
                    params, state, pstate, None, token, active,
                    remaining, keys, prefilled, prompt_len, prompt_buf,
                    credits, mig_caps, poison)

        self._step_jit = jax.jit(step_fn, donate_argnums=(1, 2))
        self._chunk_jit = jax.jit(chunk_fn, donate_argnums=(1, 2))
        self._gen_jit = jax.jit(gen_fn, donate_argnums=(1, 2),
                                static_argnums=(4,))
        #: mesh placements for serve-stream inputs (params / cache /
        #: policy state), set when a mesh is attached (serve() applies
        #: them with jax.device_put before the first chunk)
        self._serve_place = None
        if serveable and self.mesh is not None:
            self._build_sharded_serve_jit(serve_chunk_fn)
        else:
            if serveable:
                # overlap additionally donates the staged-plan carry
                # (small, but donation keeps the carry a fixed point)
                donate = (1, 2, 3) if overlap else (1, 2)
                self._serve_jit = jax.jit(serve_chunk_fn,
                                          donate_argnums=donate)
            self._release_jit = jax.jit(control.release_lanes,
                                        donate_argnums=(0,))

    def _build_sharded_serve_jit(self, serve_chunk_fn):
        """Pin the fused serve chunk's shardings on `self.mesh`.

        Explicit `in_shardings`/`out_shardings` rather than trusting
        GSPMD's defaults, for three reasons: (1) the donated carries
        (cache, policy state) must come back in EXACTLY the sharding
        they went in, or chunk-to-chunk re-layout would defeat donation
        and could oscillate into retraces — pinning out == in makes the
        sharding a fixed point; (2) host-built chunk inputs (tokens,
        masks, the prompt buffer) are uncommitted numpy uploads, so the
        in_shardings place them lane-sharded for free; (3) the rules
        themselves are the documented surface (EXPERIMENTS.md
        §Mesh-sharding) — KV pools over kv_heads or pages, lanes over
        `data`, fault caps replicated. Stats outputs stay unpinned
        (`None`): they are read back to host each boundary either way.
        """
        from repro.launch import shardings as shd
        mesh, model, geo = self.mesh, self.model, self.geo
        sh = shd.serve_shardings(geo, mesh)
        pshard = shd.param_shardings(model.logical_axes(),
                                     model.abstract_params(), mesh,
                                     "serve")
        pstate_abs = jax.eval_shape(
            lambda: self._policy.init_state(geo))
        psh = shd.policy_state_shardings(pstate_abs, geo, mesh)
        lane, lane_kv = sh["lane"], sh["lane_kv"]
        rep, step_lane = sh["rep"], sh["step_lane"]
        cache_sh = sh["cache"]
        if self.cfg.overlap_migrations:
            # the staged-plan carry is a new donated leaf: replicated
            # ([M] row vectors — the fault plane's convention: plans
            # are global control state, not per-shard), out == in so
            # the carry sharding is a fixed point; `stale` is a
            # per-lane boundary input
            plan_sh = sh["plan"]
            in_sh = (pshard, cache_sh, psh, plan_sh, lane, lane, lane,
                     lane_kv, lane, lane, lane_kv, rep, lane, rep,
                     step_lane)
            out_sh = (cache_sh, psh, plan_sh, lane, lane, lane, lane_kv,
                      lane, rep, step_lane, step_lane, step_lane,
                      step_lane, None)
            donate = (1, 2, 3)
        else:
            in_sh = (pshard, cache_sh, psh, lane, lane, lane, lane_kv,
                     lane, lane, lane_kv, rep, rep, step_lane)
            out_sh = (cache_sh, psh, lane, lane, lane, lane_kv, lane,
                      rep, step_lane, step_lane, step_lane, step_lane,
                      None)
            donate = (1, 2)

        pool_spec = shd.pool_pspec(geo, mesh)

        def meshed_chunk_fn(*args):
            # the Pallas kernel runs per shard on the pools' own layout
            # (GSPMD cannot partition it)
            with ops.sharded_pools(mesh, pool_spec):
                return serve_chunk_fn(*args)

        self._serve_jit = jax.jit(meshed_chunk_fn, donate_argnums=donate,
                                  in_shardings=in_sh,
                                  out_shardings=out_sh)
        self._release_jit = jax.jit(control.release_lanes,
                                    donate_argnums=(0,),
                                    in_shardings=(cache_sh, lane),
                                    out_shardings=cache_sh)
        self._serve_place = {"params": pshard, "cache": cache_sh,
                             "pstate": psh, "rep": rep,
                             "plan": sh["plan"]}

    # ------------------------------------------------------------------ #
    # drive modes
    # ------------------------------------------------------------------ #
    def step(self, token: jax.Array) -> jax.Array:
        """Eager: one device dispatch + one telemetry sync per token."""
        logits, self.state, self._pstate, stats = self._step_jit(
            self.params, self.state, self._pstate, token)
        self._record(tuple(np.asarray(x)[None] for x in stats))
        return logits

    def run(self, tokens: jax.Array) -> jax.Array:
        """Fused teacher-forced decode. tokens [K, B] -> logits [K, B, V].

        Runs `lax.scan` chunks of `telemetry_stride` steps; telemetry is
        read back once per chunk. Produces bitwise-identical logits and
        identical StepStats accounting to K calls of `step()`.
        """
        K = tokens.shape[0]
        if K == 0:
            return jnp.zeros((0, tokens.shape[1], self.model.cfg.vocab))
        stride = max(1, self.cfg.telemetry_stride)
        out = []
        for s in range(0, K, stride):
            self.state, self._pstate, logits, stats = self._chunk_jit(
                self.params, self.state, self._pstate,
                tokens[s:s + stride])
            self._record(tuple(np.asarray(x) for x in stats))
            out.append(logits)
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)

    def generate(self, token: jax.Array, steps: int) -> jax.Array:
        """Fused greedy generation from `token` [B] -> tokens [steps, B]."""
        if steps == 0:
            return jnp.zeros((0,) + token.shape, jnp.int32)
        stride = max(1, self.cfg.telemetry_stride)
        out = []
        done = 0
        while done < steps:
            n = min(stride, steps - done)
            self.state, self._pstate, token, toks, stats = self._gen_jit(
                self.params, self.state, self._pstate, token, n)
            self._record(tuple(np.asarray(x) for x in stats))
            out.append(toks)
            done += n
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)

    # ------------------------------------------------------------------ #
    # continuous-batching serve loop (the headline API)
    # ------------------------------------------------------------------ #
    def serve(self, requests: Sequence[Request], *,
              num_slots: Optional[int] = None,
              sampling: Optional[SamplingConfig] = None,
              seed: int = 0, total_pages: Optional[int] = None,
              max_skips: int = 8,
              faults: Optional[FaultPlane] = None,
              slo: Optional[SLOPolicy] = None) -> ServeReport:
        """Drive a request stream end-to-end through the fused hot path.

        A fixed batch of `num_slots` cache lanes runs as ONE jitted
        `lax.scan` chunk per `telemetry_stride` steps of MIXED
        prefill+decode steps: decoding lanes emit one sampled token
        while prefilling lanes consume a `prefill_chunk`-token slice of
        their prompt, written straight into their lane's pages at an
        offset (`Model.prefill_chunk`). The per-lane mode flip —
        including sampling the request's first token at the step
        prefill crosses prompt_len — happens on device, so admissions,
        mode transitions and completions never change traced shapes:
        ONE serve-chunk executable across the whole stream, whatever
        the prompt-length mix (no per-length admission compiles, no
        whole-batch stall while a prompt prefills).

        Per chunk boundary the host: reads back emitted + first tokens
        and the per-slot (active, remaining, prefilled) carry, completes
        finished requests (EOS or budget, decided ON DEVICE) with one
        masked `control.release_lanes` call covering every completion
        in the chunk, and admits queued requests — pure bookkeeping
        (`_admit_lane`): a prompt row, counters, and a sampling key.
        Every host statement runs in exactly one phase (`_Phases`):
        the profiler spans `serve.setup`, then per chunk `upload`,
        `dispatch`, `readback`, `account`, `reap`, `release`, `admit`
        (and `wait` in an idle open loop), then `report`, which tile
        the call. `ServeReport.chunks` keeps a `ServeChunk` per chunk:
        its phase seconds and the lane counts its outputs show.

        Sampling (temperature / top-k / top-p) runs inside the fused
        loop with per-slot PRNG keys derived from (`seed`, request id);
        the default `SamplingConfig()` is greedy, and a single
        full-length request then reproduces `generate` bitwise — as
        does chunked prefill at ANY budget vs the whole-prompt forward
        (tests/test_chunked_prefill.py).

        Returns a `ServeReport`: completed requests (token ids in
        `req.output`) plus TTFT/TPOT percentiles from the per-request
        wall-clock stamps.

        With `EngineConfig.trace_telemetry` the chunk additionally
        reads back every lane's page read set and read-time placement
        (decode plane only — prefill writes never enter the access
        model) stamped with the chunk's lane->request bindings;
        `trace_bridge.collect_serve`/`attribute` stitch those into
        per-request simulator traces and `trace_bridge.score_serve`
        scores the stream (and each request) against the SA upper
        bound. Capture is pure observation: tokens, StepStats, and the
        one-executable-per-stream property are unchanged.

        Failure semantics (see `repro.serving.faults` and
        EXPERIMENTS.md §Fault-injection): serve NEVER raises on a
        per-request condition. Invalid requests (missing prompt,
        `max_new_tokens < 1`, prompt+budget over the cache capacity),
        duplicates, and pool-infeasible footprints are REJECTED with a
        typed error while the rest of the stream proceeds; per-request
        `deadline_s` and `cancel()` are honored at chunk boundaries
        ("timeout"/"cancelled" — live lanes release their pages, queued
        requests are dropped); a lane whose logits go non-finite is
        quarantined on device and completed as "failed". Every request
        ends in exactly one terminal status (`ServeReport.statuses`).

        With `EngineConfig.overlap_migrations` the migration plane runs
        as a two-phase, double-buffered pipeline inside the same scan:
        each step COMMITS the plan staged at the previous step
        (revalidated against the current owner maps and throttled by
        the fault channel) concurrently with its decode compute, then
        PLANS for the next step using this step's read set as a
        one-step-ahead re-reference oracle (`DevicePolicy.plan_ahead`,
        active when the read set is sparse). Decode SEMANTICS are
        placement-invariant — attention reads the same pages wherever
        they reside — so overlap mode computes the same stream; when
        interim placements differ from the inline engine's, the
        per-tier LSE merge may associate floating point differently
        (the serve==generate bitwise pin is inline-mode). The
        zero-retrace / one-executable pins hold per (policy, mesh,
        overlap). See EXPERIMENTS.md §Async-migration.

        Constructed with a device mesh (`ServingEngine(..., mesh=m)`),
        the SAME loop runs sharded: the chunk executable is compiled
        with pinned `NamedSharding`s (KV pools tensor-parallel over
        kv_heads or pages, lanes data-parallel, fault caps replicated
        — `repro.launch.shardings.serve_shardings`), the cache /
        policy-state carries stay device-resident and donated per
        shard, and boundary readbacks gather transparently. Placement
        is values-only, so the zero-retrace and one-executable pins
        hold per mesh, and tokens + terminal statuses match the
        single-device stream (tests/test_mesh_serve.py; EXPERIMENTS.md
        §Mesh-sharding).

        Open-loop traffic: a request with `arrival_s > 0` is held back
        and SUBMITTED at the first chunk boundary whose wall clock
        (relative to stream start) passes its arrival offset — the
        workload plane's load driver (`benchmarks/workloads.py`). The
        arrival pattern is pure DATA: bursty, diurnal, and Poisson
        streams all drive the same serve-chunk executable (an idle
        stream with pending arrivals sleeps between boundaries; shapes
        never change; tests/test_workloads.py). `queue_wait_s` then
        measures real queueing.

        `slo` layers SLO-aware admission on top of the
        `prefill_budget` token bucket: at every chunk boundary, AFTER
        deadline/cancel reaping, each QUEUED request's earliest
        achievable TTFT is projected (wait so far + prompt prefill at
        the measured per-step cadence) and requests past their tier's
        target are shed as `rejected` with error code "slo_shed" —
        early, before they cost a lane or drag decode TPOT. A request
        is never counted both "timeout" and SLO-shed: deadline reaping
        runs first and removes it from the queue. Per-request TTFT
        decomposition (`queue_wait_s` + `prefill_s` + `throttle_s` ==
        TTFT, exact at the chunk-stride stamp resolution) lands on
        every admitted request; `ServeReport.ttft_parts` carries
        the percentiles and `slo.score_goodput` turns a report + an
        `SLOPolicy` into the goodput row.

        `faults` optionally injects a deterministic adversity schedule
        (`FaultPlane`): tier-bandwidth degradation reprices telemetry
        under the degraded spec and recalibrates cost_aware paybacks;
        migration faults throttle plan commits; pool faults resize the
        scheduler's page pool; poison faults NaN a lane's logits. The
        fault channel is compiled into the serve executable as DATA
        (per-step caps + poison masks), so a clean run and a faulted
        run share ONE executable and fault-free lanes produce bitwise
        identical tokens. Degradations are stamped into
        `ServeReport.events`, and repeated commit drops or a tier
        ratio past `EngineConfig.fallback_tier_ratio` degrade the
        policy to static behavior (all commits masked) for the rest of
        the stream.
        """
        cfg = self.cfg
        fam = self.model.cfg.family
        if fam not in ("dense", "moe"):
            raise NotImplementedError(
                f"serve() drives cache-backed decode states (dense/moe); "
                f"family {fam!r} needs prefill extras or recurrent-state "
                f"lane insertion")
        if not requests:
            return ServeReport(completed=[])
        #: the call's host phases; each chunk's record takes their
        #: seconds since the previous chunk's readback
        phases = _Phases()
        phases.to("setup")
        chunks: List[ServeChunk] = []
        B = num_slots if num_slots is not None else min(len(requests), 4)
        geo = self._prepare_serve(B, sampling)
        # both tiers are device arrays in every mode: on a TPU the host
        # tier is HBM-resident until ROADMAP queue 1 item 2 lands; on a
        # mesh each device makes its own shard (the whole may not fit one)
        self.state = init_cache(geo) if self._serve_place is None else \
            jax.jit(init_cache, static_argnums=0,
                    out_shardings=self._serve_place["cache"])(geo)
        self.stats = []
        pstate = self._policy.init_state(geo)
        if self._serve_place is not None:
            # mesh placement, once per stream: the policy state (a
            # donated carry) and the params to the exact shardings the
            # serve jit pins — every later chunk then reuses the placement
            # (device_put on an already-matching pytree is a no-op)
            pstate = jax.device_put(pstate, self._serve_place["pstate"])
            self.params = jax.device_put(self.params,
                                         self._serve_place["params"])
        credits = jnp.zeros((), jnp.int32)   # prefill token bucket
        if self._serve_place is not None:
            # committed-replicated from chunk one, like every later
            # chunk's device output — an uncommitted first value would
            # fork the jit's input-sharding cache key (2 entries, same
            # lowering) and break the one-executable pin
            credits = jax.device_put(credits, self._serve_place["rep"])
        #: per-chunk (access, tier, emitted, first, rids, prompt_len)
        #: when cfg.trace_telemetry (trace_bridge.collect_serve)
        self._serve_trace_log = []

        pool = total_pages if total_pages is not None \
            else B * geo.max_pages
        batcher = ContinuousBatcher(B, pool, page_tokens=geo.page_tokens,
                                    max_skips=max_skips)
        self.batcher = batcher
        # per-request validation: an invalid request is REJECTED with a
        # typed error; everyone else keeps serving (no batch-wide abort)
        def submit_one(r: Request) -> None:
            if r.prompt is None:
                batcher.reject_submit(
                    r, "empty_prompt",
                    f"request {r.rid}: serve() needs prompt tokens")
            elif r.max_new_tokens < 1:
                batcher.reject_submit(
                    r, "zero_budget",
                    f"request {r.rid}: max_new_tokens must be >= 1")
            elif r.prompt_len + r.max_new_tokens > geo.max_tokens:
                batcher.reject_submit(
                    r, "infeasible_context",
                    f"request {r.rid}: {r.prompt_len}+{r.max_new_tokens}"
                    f" tokens exceed cache capacity {geo.max_tokens}")
            else:
                batcher.submit(r)   # may itself reject (duplicate /
                #                     pool-infeasible footprint)

        # open-loop load driver: requests with a positive arrival
        # offset are held back and submitted at the first chunk
        # boundary whose wall clock passes them — `submitted_at` (and
        # so queue_wait/TTFT) stamps at ARRIVAL, not at serve() entry
        t_start = time.time()
        pending: List[Request] = sorted(
            (r for r in requests if r.arrival_s > 0.0),
            key=lambda r: r.arrival_s)
        for r in requests:
            if r.arrival_s <= 0.0:
                submit_one(r)

        def submit_arrivals() -> bool:
            now_rel = time.time() - t_start
            due = False
            while pending and pending[0].arrival_s <= now_rel:
                submit_one(pending.pop(0))
                due = True
            return due

        # fault plumbing: a neutral plane keeps the (always-compiled)
        # fault channel at identity values for clean runs
        faults = faults if faults is not None else FaultPlane()
        base_spec = cfg.spec
        cap_rows = control.plan_capacity(geo, cfg.migration_budget_frac)
        events: List[dict] = []
        last_spec = base_spec
        fallback = False
        drop_streak = 0
        # overlap mode: the staged-plan scan carry starts as an all
        # sentinel (empty) plan — step 0 commits nothing, exactly the
        # one-step pipeline fill; `stale_np` marks lanes the host
        # rebound between chunks so their staged rows get masked
        staged = None
        stale_np = np.zeros((B,), bool)
        if cfg.overlap_migrations:
            staged = MigrationPlan.empty(cap_rows)
            if self._serve_place is not None:
                staged = jax.device_put(staged, self._serve_place["plan"])

        stride = max(1, cfg.telemetry_stride)
        hs = self._lane_host_state(geo, seed)
        live: Dict[int, Request] = {}          # lane -> request

        def admit():
            """Admit queued requests until the batcher admits none."""
            while True:
                admitted = batcher.admit()
                if not admitted:
                    return
                for req in admitted:
                    self._admit_lane(req, hs)
                    if req.lane >= 0:
                        live[req.lane] = req
                        # overlap: a freshly (re)bound lane's staged
                        # rows describe the PREVIOUS tenant — and
                        # deterministic static placement means a
                        # re-admission can reproduce the evicted
                        # request's exact (slot, logical) pairs, so
                        # commit-time revalidation alone cannot tell
                        # them apart. Mark the lane stale; the chunk
                        # masks its rows before anything commits.
                        stale_np[req.lane] = True

        #: EMA of the measured per-step wall seconds (from chunk
        #: spans) — the SLO shed projection's prefill-cadence estimate
        est_step_s: Optional[float] = None

        def shed_slo() -> None:
            """SLO-aware admission: project each QUEUED request's
            earliest achievable TTFT and shed hopeless ones as
            `rejected` / "slo_shed". Runs after deadline/cancel
            reaping, so "timeout" and SLO-shed are mutually exclusive
            by construction (both remove the request from the queue).
            """
            if slo is None:
                return
            now = time.time()
            for req in list(batcher.queue):
                # an expired or cancelled request belongs to the
                # reaper: never convert a due "timeout"/"cancelled"
                # into an SLO shed
                if req.cancel_requested or (
                        req.deadline_s is not None
                        and now - req.submitted_at > req.deadline_s):
                    continue
                reason = slo.should_shed(req, now, est_step_s,
                                         cfg.prefill_chunk)
                if reason is not None:
                    batcher.drop_queued(req, "rejected", "slo_shed",
                                        reason)
                    events.append({"kind": "slo_shed",
                                   "step": batcher.step_idx,
                                   "rid": req.rid, "tier": req.tier,
                                   "reason": reason})

        # stream start: admit FIRST (nobody has genuinely waited yet),
        # then shed the queued remainder that already cannot make it
        admit()
        shed_slo()
        view = batcher.device_view()
        n_bound = 0                            # admissions chunked so far
        while batcher.has_work or pending:
            phases.to("admit")
            if submit_arrivals():
                admit()
                shed_slo()
                view = batcher.device_view()
            if not view.active.any():
                if batcher.queue:
                    # nothing live but work queued: the head can't be
                    # admitted with every page free (footprint vs a
                    # possibly shrunken pool) — reject it and move on
                    # instead of killing the stream mid-flight
                    stuck = batcher.queue.popleft()
                    batcher.reject(
                        stuck, "admission_stalled",
                        f"needs {stuck.pages_needed} pages, pool has "
                        f"{batcher.free_pages}/{batcher.total_pages} free")
                    admit()
                    view = batcher.device_view()
                    continue
                if pending:
                    # idle stream with future arrivals (open loop):
                    # sleep toward the next one, bounded so the
                    # boundary cadence stays responsive
                    phases.to("wait")
                    wait = pending[0].arrival_s - (time.time() - t_start)
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
                    continue
                break
            phases.to("upload")
            step0 = batcher.step_idx
            events.extend(faults.window_events(step0, stride))
            # tier fault: reprice + recalibrate under the spec that
            # governs this chunk; past the ratio threshold, migrating
            # toward the host tier can't pay back — fall back to static
            spec_now = faults.spec_at(step0, base_spec)
            if spec_now != last_spec:
                pstate = self._policy.recalibrate(pstate, spec_now)
                if self._serve_place is not None:
                    # recalibrated values are fresh host scalars —
                    # restore the pinned placement so the chunk jit's
                    # input-sharding key (and the one-executable pin)
                    # survives the boundary
                    pstate = jax.device_put(pstate,
                                            self._serve_place["pstate"])
                last_spec = spec_now
                events.append({
                    "kind": "payback_recalibration", "step": step0,
                    "bw_ratio": spec_now.bw_ratio})
            if not fallback and spec_now.bw_ratio >= \
                    cfg.fallback_tier_ratio * base_spec.bw_ratio:
                fallback = True
                events.append({
                    "kind": "policy_fallback", "step": step0,
                    "reason": "tier_ratio",
                    "bw_ratio": spec_now.bw_ratio})
            caps_np = faults.commit_caps(step0, stride, cap_rows)
            if (caps_np == 0).any():
                drop_streak += 1
            else:
                drop_streak = 0
            if not fallback and \
                    drop_streak >= max(1, cfg.fallback_commit_faults):
                fallback = True
                events.append({
                    "kind": "policy_fallback", "step": step0,
                    "reason": "commit_faults",
                    "boundaries": drop_streak})
            if fallback:
                # static fallback as DATA: all commits masked — the
                # same executable keeps running, it just stops moving
                # pages (exactly the registered `static` policy's
                # behavior: plans exist, none commit)
                caps_np = np.zeros_like(caps_np)
            poison_np = faults.poison_steps(step0, stride, view.rids)
            t0 = time.time()
            # TTFT decomposition anchor: a lane's clock switches from
            # queue_wait to prefill/throttle the instant its first
            # chunk starts running
            for req in live.values():
                if req.admitted_at is None:
                    req.admitted_at = t0
            args = self._chunk_args(
                self.state, pstate, staged, hs, view, credits, stale_np,
                caps_np, poison_np)
            queue_depth = len(batcher.queue)
            phases.to("dispatch")
            t_dispatch = time.time()
            outs = self._serve_jit(*args)
            phases.to("readback")
            if cfg.overlap_migrations:
                self.state, pstate, staged, *outs = outs
                # the chunk consumed the staleness marks; releases /
                # admissions below repopulate them for the next chunk
                stale_np = np.zeros((B,), bool)
            else:
                self.state, pstate, *outs = outs
            (tok_d, act_d, _rem_d, keys_d, prog_d, credits, emitted,
             first, failed, pf_d, stats) = outs
            emitted = np.asarray(emitted)               # [stride, B]
            first = np.asarray(first)                   # [stride, B]
            pf_tok = np.asarray(pf_d)                   # [stride, B]
            failed_lane = np.asarray(failed).any(axis=0)      # [B]
            hs["token"] = np.array(tok_d)               # writable copies:
            hs["keys"] = np.array(keys_d)               # admit() pokes them
            prog = np.asarray(prog_d)
            done_d = ~np.asarray(act_d)
            t_ready = time.time()
            phases.to("account")
            # telemetry: only steps where at least one lane DECODED —
            # prefill-only steps (first tokens included) are charged to
            # the prefill stage, matching the simulator's convention;
            # under a tier fault each surviving row is priced with the
            # spec governing ITS step
            row_mask = emitted.max(axis=1) >= 0
            specs = None
            if faults.tier:
                specs = [faults.spec_at(step0 + i, base_spec)
                         for i in np.nonzero(row_mask)[0]]
            self._record((np.asarray(stats[0])[row_mask],), specs=specs)
            if len(stats) == 3:
                # serve trace capture: the full-batch read set + tiers,
                # stamped with the chunk's lane->request bindings (fixed
                # within a chunk: admission only happens at boundaries)
                self._serve_trace_log.append(
                    (np.asarray(stats[1]), np.asarray(stats[2]),
                     emitted, first, view.rids.copy(),
                     view.prompt_len.copy()))
            # per-step wall-clock stamps: the chunk's device events are
            # observed at the boundary, so spread its wall time evenly
            # over the stride — TTFT/TPOT then resolve WITHIN a chunk
            # (a request finishing in one chunk still gets a per-token
            # latency, not a ~0 boundary-to-boundary delta)
            span = time.time() - t0
            est = span / stride
            est_step_s = est if est_step_s is None else \
                0.5 * (est_step_s + est)

            def stamp(row):
                return t0 + (row + 1) / stride * span
            chunks.append(ServeChunk(
                t_dispatch=t_dispatch, t_ready=t_ready,
                stamps=np.array([stamp(s) for s in range(stride)]),
                phase_s=phases.take(), rids=view.rids.copy(),
                decoding=(emitted >= 0).sum(axis=1),
                prefilling=(pf_tok > 0).sum(axis=1),
                prompt_tokens=pf_tok.sum(axis=1),
                admitted=len(batcher.bindings) - n_bound,
                queue_depth=queue_depth))
            n_bound = len(batcher.bindings)

            release = np.zeros((B,), bool)
            for lane, req in list(live.items()):
                # a lane never emits both in one step: `first` at the
                # crossing step, `emitted` at decode steps after it
                rows = np.where(first[:, lane] >= 0, first[:, lane],
                                emitted[:, lane])
                got = np.nonzero(rows >= 0)[0]
                if req.first_token_at is None and \
                        req.admitted_at is not None:
                    # TTFT attribution up to the crossing row: rows
                    # where the lane ran prefill tokens are charged to
                    # prefill_s, budget-throttled rows (token bucket
                    # held the lane back) to throttle_s, and any host
                    # gap since the cursor (queue->dispatch, boundary
                    # work between chunks) to throttle_s as well — so
                    # queue_wait + prefill + throttle == TTFT exactly
                    crossed = first[:, lane].max() >= 0
                    c = int(np.argmax(first[:, lane] >= 0)) \
                        if crossed else stride - 1
                    cursor = (req.admitted_at + req.prefill_s +
                              req.throttle_s)
                    req.throttle_s += max(0.0, t0 - cursor)
                    ran = int((pf_tok[:c + 1, lane] > 0).sum())
                    w = span / stride
                    req.prefill_s += ran * w
                    req.throttle_s += (c + 1 - ran) * w
                if req.first_token_at is None and first[:, lane].max() >= 0:
                    req.first_token_at = stamp(
                        int(np.argmax(first[:, lane] >= 0)))
                    req.first_token_read_at = t_ready
                    req.phase = "decoding"
                req.output.extend(int(rows[s]) for s in got)
                req.generated += len(got)
                req.prefilled = int(min(prog[lane], req.prompt_len))
                if done_d[lane]:      # EOS/budget/quarantine, on device
                    del live[lane]
                    release[lane] = True
                    if failed_lane[lane]:
                        # non-finite logits quarantined this lane: no
                        # token was emitted from the poisoned step on,
                        # pages release below, the stream keeps serving
                        batcher.complete(req, "failed", RequestError(
                            "poisoned_logits",
                            f"non-finite logits on lane {lane}"))
                    else:
                        req.stop_reason = "eos" if (
                            cfg.eos_id is not None and req.output
                            and req.output[-1] == cfg.eos_id) \
                            else "budget"
                        batcher.complete(req)
                    if got.size:
                        req.finished_at = stamp(int(got[-1]))
            phases.to("reap")
            # deadline + cooperative cancellation, at chunk-boundary
            # granularity: reaped lanes release pages like any other
            # completion; queued requests are dropped before admission
            now = time.time()
            for lane, req in list(live.items()):
                timed_out = req.deadline_s is not None and \
                    now - req.submitted_at > req.deadline_s
                if not (req.cancel_requested or timed_out):
                    continue
                status = "cancelled" if req.cancel_requested else "timeout"
                del live[lane]
                release[lane] = True
                batcher.complete(req, status, RequestError(
                    "cancelled" if status == "cancelled"
                    else "deadline_exceeded",
                    f"reaped at step {batcher.step_idx + stride}"))
            for req in [q for q in batcher.queue
                        if q.cancel_requested or
                        (q.deadline_s is not None and
                         now - q.submitted_at > q.deadline_s)]:
                status = "cancelled" if req.cancel_requested else "timeout"
                batcher.drop_queued(
                    req, status,
                    "cancelled" if status == "cancelled"
                    else "deadline_exceeded",
                    "reaped while queued")
            # a released lane's staged plan rows are garbage for any
            # successor tenant — stale until the next chunk masks them
            stale_np |= release
            phases.to("release")
            chunks[-1].released = int(release.sum())
            if release.any():
                # ONE masked release per boundary covers every
                # completion in the chunk — including instant
                # budget-1/EOS crossings, which used to cost a separate
                # device call each at admission
                self.state = self._release_jit(self.state,
                                               jnp.asarray(release))
            phases.to("admit")
            delta = faults.pool_delta(step0, stride)
            if delta:
                batcher.resize_pool(delta)
            batcher.step_idx += stride
            # SLO shedding runs AFTER deadline/cancel reaping (so a
            # request is never both "timeout" and SLO-shed) and before
            # admission refills the freed lanes
            shed_slo()
            admit()
            view = batcher.device_view()
        phases.to("report")
        report = ServeReport.build(batcher.completed, batcher.rejected,
                                   events, eos_id=cfg.eos_id,
                                   chunks=chunks)
        phases.to(None)
        return report

    def _prepare_serve(self, num_slots: int,
                       sampling: Optional[SamplingConfig]):
        """Set the cache geometry and sampling of a `num_slots`-lane
        stream and build its step functions; returns the geometry."""
        cfg = self.cfg
        self.geo = self.model.cache_geometry(
            num_slots, cfg.max_context, hbm_fraction=cfg.hbm_fraction)
        self._sampling = sampling or SamplingConfig()
        self._ensure_step_fns()
        return self.geo

    @staticmethod
    def _lane_host_state(geo, seed: int) -> Dict:
        """Host-side lane state poked by `_admit_lane`; everything the
        device needs is re-uploaded per chunk (small [B]-vectors plus
        the [B, max_tokens] prompt buffer)."""
        root = jax.random.PRNGKey(seed)
        return {
            "root": root,
            "prompt_buf": np.zeros((geo.batch, geo.max_tokens), np.int32),
            "token": np.zeros((geo.batch,), np.int32),
            "keys": np.array(jax.random.split(root, geo.batch)),
        }

    def _chunk_args(self, state, pstate, staged, hs: Dict, view,
                    credits, stale, caps, poison) -> tuple:
        """One serve-chunk call's arguments, in `_serve_jit`'s order
        (`staged` and `stale` only in overlap mode)."""
        lanes = (jnp.asarray(hs["token"]), jnp.asarray(view.active),
                 jnp.asarray(view.remaining), jnp.asarray(hs["keys"]),
                 jnp.asarray(view.prefilled), jnp.asarray(view.prompt_len),
                 jnp.asarray(hs["prompt_buf"]), credits)
        faults = (jnp.asarray(caps), jnp.asarray(poison))
        if self.cfg.overlap_migrations:
            return (self.params, state, pstate, staged, *lanes,
                    jnp.asarray(stale), *faults)
        return (self.params, state, pstate, *lanes, *faults)

    def serve_chunk_shapes(self, num_slots: int, sharding=None) -> tuple:
        """The argument shapes of one serve-chunk call for `num_slots`
        lanes with greedy sampling: prepares the engine as `serve()`
        does, then builds the first chunk's arguments with `serve()`'s
        own code. `self._serve_jit.lower(*shapes).compile()` then
        compiles the chunk ahead of a stream, for example for a
        described chip (`sharding` on one of its devices) whose arrays
        cannot exist here. `self.params` may be concrete or shapes."""
        cfg = self.cfg
        geo = self._prepare_serve(num_slots, None)
        stride = max(1, cfg.telemetry_stride)
        cap_rows = control.plan_capacity(geo, cfg.migration_budget_frac)
        view = ContinuousBatcher(num_slots, num_slots * geo.max_pages,
                                 page_tokens=geo.page_tokens).device_view()
        faults = FaultPlane()
        staged = (MigrationPlan.empty(cap_rows)
                  if cfg.overlap_migrations else None)
        args = self._chunk_args(
            abstract_cache(geo),
            jax.eval_shape(lambda: self._policy.init_state(geo)), staged,
            self._lane_host_state(geo, 0), view, jnp.zeros((), jnp.int32),
            np.zeros((num_slots,), bool),
            faults.commit_caps(0, stride, cap_rows),
            faults.poison_steps(0, stride, view.rids))
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), args)

    def _admit_lane(self, req: Request, hs: Dict) -> None:
        """Bind an admitted request to its cache lane for CHUNKED
        prefill: pure host bookkeeping — the prompt row, the progress
        counters (via `req.prefilled`, exported by `device_view`), and
        the lane's sampling key. No device compute, no model forward,
        no per-prompt-length compiles; the prompt starts flowing into
        the lane's pages at the next chunk's mixed steps."""
        lane = req.lane
        prompt = np.asarray(req.prompt).astype(np.int32).ravel()
        hs["prompt_buf"][lane, :] = 0
        hs["prompt_buf"][lane, :prompt.size] = prompt
        hs["token"][lane] = 0
        hs["keys"][lane] = np.asarray(
            lane_key(hs["root"], jnp.int32(req.rid)))

    # ------------------------------------------------------------------ #
    # telemetry (host side, Eq. (1)-(5) pricing)
    # ------------------------------------------------------------------ #
    def _record(self, stats, specs=None):
        """Price a batch of per-step device telemetry into `self.stats`.

        stats: a tuple off the device — `(base,)` or, with
        `cfg.trace_telemetry`, `(base, access, tier)` where base is
        [n, 4] int32 rows of (hbm_pages, host_pages, promotes, demotes)
        and access/tier are the per-step [n, L, B, P] page read set and
        placement; lane 0 is kept raw for the single-stream bridge
        (`trace_bridge.collect` — serve capture goes through
        `_serve_trace_log` instead, with all lanes).

        `specs`: optional per-row `MemorySystemSpec` list — under a
        tier fault, `serve` prices each surviving row with the
        (degraded) spec governing its step instead of `cfg.spec`, so
        the modeled latency of a degraded window is honest."""
        if len(stats) == 3:
            self._trace_log.append(
                (stats[0], stats[1][:, :, 0], stats[2][:, :, 0]))
        stats = stats[0]
        geo = self.geo
        pb = geo.page_bytes()
        frac = 1.0 - self.cfg.attention_sparsity
        for i, (h_pages, e_pages, n_pro, n_dem) in enumerate(stats):
            spec = specs[i] if specs is not None else self.cfg.spec
            traffic = dict(
                h_read=float(h_pages) * pb * frac,
                e_read=float(e_pages) * pb * frac,
                m_in=float(n_pro) * pb, m_out=float(n_dem) * pb,
                h_write=pb / geo.page_tokens, e_write=0.0)
            lat = float(step_latency(StepTraffic(**traffic), spec))
            denom = traffic["h_read"] + traffic["e_read"]
            self.stats.append(StepStats(
                modeled_latency_s=lat,
                h_read=traffic["h_read"], e_read=traffic["e_read"],
                m_in=traffic["m_in"], m_out=traffic["m_out"],
                hbm_hit_rate=traffic["h_read"] / denom if denom else 1.0,
                resident_pages=int(h_pages) + int(e_pages)))

    def summary(self) -> Dict[str, float]:
        """Aggregate the recorded StepStats: step count, modeled total
        seconds and tokens/s, mean HBM hit rate, migrated bytes, and
        `kernel_slot_share`.

        `kernel_slot_share` is the share of the two tiers' pool slots
        that hold a page, Σ resident pages / (steps × L × B × (Ph + Pe)):
        the entries the paged kernel walks in a decode step. Its DMAs
        round each lane's walk up to whole blocks, at most
        `pages_per_block` − 1 more entries a lane, layer and tier; pages
        a Quest mask skips are counted here but not walked. It reads 0
        on an engine that ran no step."""
        if not self.stats:
            return {"kernel_slot_share": 0.0}
        lat = np.array([s.modeled_latency_s for s in self.stats])
        geo = self.geo
        slots = geo.num_layers * geo.batch * geo.max_pages
        return {
            "steps": len(self.stats),
            "modeled_total_s": float(lat.sum()),
            "modeled_tokens_per_s": len(lat) / float(lat.sum()),
            "mean_hbm_hit_rate": float(np.mean(
                [s.hbm_hit_rate for s in self.stats])),
            "migrated_bytes": float(sum(s.m_in + s.m_out
                                        for s in self.stats)),
            "kernel_slot_share": sum(s.resident_pages for s in self.stats)
            / (len(self.stats) * slots),
        }
