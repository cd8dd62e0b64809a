"""Wall-clock decode throughput for the live serving engine.

Three drive modes over the SAME model and two-tier paged cache:

  host   — replica of the pre-fusion engine step: per-step host
           round-trips of page_table/owners/importance, nested Python
           [L, B] loops for write-slot choice and migration planning,
           and a `MigrationPlan` whose capacity varies with the step's
           promote/demote count (so `apply_migrations` recompiles for
           nearly every distinct count). This is the baseline the fused
           hot path was built to kill — kept here, not in the engine,
           so the win stays measurable PR over PR.
  eager  — `ServingEngine.step`: the whole step (vectorized control
           plane + decode + fixed-capacity migration) is ONE jitted
           call, but the host dispatches and syncs telemetry per token.
  fused  — `ServingEngine.generate`: `lax.scan` over telemetry_stride
           steps per dispatch, cache donated, one telemetry readback
           per chunk.

A fourth mode measures the headline serving API:

  serve  — `ServingEngine.serve`: a mixed-length request stream through
           the same fused chunks of MIXED prefill+decode steps (chunked
           prefill inside the loop), with per-slot active masking,
           on-device sampling, and chunk-boundary admission/reclaim.
           The stream spans >= 3 distinct page-rounded prompt lengths
           and the serve chunk must stay at ONE executable — admissions
           no longer compile per prompt length. TTFT/TPOT percentiles
           from the ServeReport land in BENCH_engine.json.

A fifth comparison isolates what chunked prefill bought:

  eager-admission — `EagerAdmissionEngine` replicates PR 2's admission
           (a blocking whole-prompt batch-1 forward per request, one
           compile per page-rounded prompt length, `insert_lane` copy).
           A long prompt of a FRESH page-rounded length admitted
           mid-stream shows the TTFT gap: the baseline stalls every
           decode lane behind the prompt forward (plus its compile);
           the chunked engine overlaps prefill slices with decode.

A sixth mode sweeps the POLICY PLANE (EXPERIMENTS.md §Policy-plane):

  policy-sweep — every registered device policy drives the same fused
           generate stream with `trace_telemetry` on; the simulator
           bridge (`repro.serving.trace_bridge`) scores each stream's
           achieved placement against the SA upper bound and the
           Belady oracle replayed on the SAME access pattern. Per
           policy: wall-clock steps/s, HBM hit fraction,
           fraction-of-SA-upper-bound, headroom vs static — plus the
           one-executable-per-policy assert (swapping policies swaps a
           traced function, never the architecture).

A seventh scores the bound where the serving traffic is
(EXPERIMENTS.md §Serve-trace):

  serve-sweep — every registered policy drives the same mixed
           continuous-batching `serve` stream with `trace_telemetry`
           on; the bridge stitches per-REQUEST traces across lane
           reuse (`collect_serve`/`attribute`) and `score_serve`
           reports the AGGREGATE stream's hit/bound fractions plus
           each request's attributed fractions — the paper's headroom
           under realistic multi-request load, not just isolated
           decode. Asserted per policy: ONE serve executable with
           capture on (telemetry adds zero retraces).

An eighth leg is the robustness smoke (EXPERIMENTS.md
§Fault-injection):

  chaos  — the SAME engine serves the same request stream clean, then
           under a seeded `FaultPlane` (tier degradation + migration
           drop + pool shrink + one poisoned lane). Asserted: serve()
           never raises, every request ends in a terminal status, the
           poisoned request ends `failed`, every fault-free request's
           tokens are BITWISE identical to its clean-run tokens, and
           the serve-chunk executable count stays at ONE across both
           runs — faults are data, not shape.

A ninth leg is the scaling surface (EXPERIMENTS.md §Mesh-sharding):

  mesh-sweep — the serve stream on host-device meshes of increasing
           size: pure data-parallel points (data=n, model=1) with lanes
           scaled to devices, plus one tensor-parallel point at the top
           count. Records wall tokens/s + TTFT/TPOT p50 per point into
           rows["mesh_sweep"]; the CI mesh leg additionally asserts ONE
           serve executable per mesh (sharding never forks the cache).
           Forced host devices share physical cores, so the curve is
           descriptive data, never a speedup gate.

A tenth leg measures what the async pipeline bought (EXPERIMENTS.md
§Async-migration):

  overlap-sweep — the contended serve-sweep stream (ctx 512 geometry,
           272/288-token prompts spilling the 16-page HBM pool, Quest
           sparsity 0.5) served inline (`overlap_migrations=False`,
           the PR 7 commit-in-step path) then overlapped (the
           double-buffered plan/commit split: step N commits the plan
           staged at N-1 concurrently with decode and plans N+1 off
           this step's read set). Records tokens/s, aggregate HBM hit
           fraction, migrated bytes, and executable counts per mode,
           plus a cost_aware + `measured_payback` leg whose
           bound_fraction is compared against the PR 5 modeled-payback
           baseline. The CI gate: overlap throughput >= 0.9x inline
           (the pipeline must never COST wall-clock; forced-host CPU
           devices can't show the real win, so the gate is a
           no-regression bound with the standard noise margin), hit
           fractions equal within +-0.01 (one step of staging lag must
           not change WHERE reads land), and ONE executable per mode.

An eleventh leg scores the stream the paper's SLOs actually see
(EXPERIMENTS.md §Workloads):

  goodput-sweep — seeded open-loop traffic from the workload plane
           (`benchmarks/workloads.py`): per policy, three
           single-pattern streams (Poisson / bursty on-off / diurnal)
           drive the SAME engine to pin ONE serve executable across
           arrival patterns (arrivals are pure data), then a mixed
           Poisson+bursty sampled stream is served with SLO-aware
           admission and scored into a goodput-under-SLO curve
           (`trace_bridge.goodput_curve`): fraction of submitted
           requests completed within per-tier targets at each target
           scale, judged on the MODELED per-request latency (Eq.
           (1)-(5) via `score_serve` request_scores — CPU wall clocks
           cannot see what placement bought, the modeled TPOT can)
           against the stream's live SA bound_fraction. Records
           rows["goodput"]; the CI gate: every request terminal, one
           executable per policy across all four streams, and
           importance mean goodput over the curve >= static at equal
           targets (the TPOT target is derived once from the static
           stream's modeled median, so both policies face the same
           contract).

Writes BENCH_engine.json (see EXPERIMENTS.md §Perf-suite; the file is
stamped with `schema_version` + the producing `commit` so trajectory
tooling can parse it). The headline is fused/host steps-per-second;
fused executable counts are asserted to stay at one compile per scan
length (zero migration-driven or admission-driven retraces).

Run:  PYTHONPATH=src python benchmarks/perf_engine.py
      PYTHONPATH=src python benchmarks/perf_engine.py --policy-sweep
      (generate + serve policy sweeps only, full geometry)
      PYTHONPATH=src python benchmarks/perf_engine.py --overlap-sweep
      (inline vs overlapped serve only, appended into rows["overlap"])
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python benchmarks/perf_engine.py --mesh-sweep
      (scaling sweep only, appended into rows["mesh_sweep"])
      PYTHONPATH=src python benchmarks/perf_engine.py --goodput-sweep
      (workload-plane goodput-under-SLO curves per policy, appended
      into rows["goodput"])
CI:   PYTHONPATH=src python benchmarks/perf_engine.py --ci
      (reduced geometry; additionally asserts fused >= eager steps/s,
      chunked-admission TTFT < eager-admission TTFT for the mid-stream
      long prompt, one executable per device policy — serve telemetry
      included — importance hit fraction >= static in the policy
      sweep, per-policy aggregate + per-request hit/bound fractions
      present in the serve sweep, the single-request serve bridge
      bitwise equal to the generate bridge, the chaos smoke's
      graceful-degradation contract above, and the overlap gate:
      overlapped serve >= 0.9x inline tokens/s at hit fractions equal
      within +-0.01, one executable per mode)
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.sa import SAConfig
from repro.core.tiers import GH200
from repro.kvcache.migrate import MigrationPlan, apply_migrations
from repro.kvcache.paged import prefill_cache
from repro.launch.jax_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving import control, trace_bridge
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.faults import (
    FaultPlane, MigrationFault, PoisonFault, PoolFault, TierFault,
)
from repro.serving.policies import policy_names
from repro.serving.scheduler import Request, TERMINAL_STATUSES
from repro.serving.slo import SLOPolicy

STEPS = 64          # multiple of STRIDE: scan lengths compile once in warmup
STRIDE = 32
HOST_STEPS = 8          # the host baseline is too slow for more

#: BENCH_engine.json layout version. Bump when keys move or change
#: meaning; trajectory tooling keys off this + the `commit` stamp.
#: v2: added serve_policy_sweep (aggregate + per-request fractions)
#: and the schema_version/commit provenance stamp itself.
#: v3: added the chaos smoke row (terminal-status counts, fault-event
#: count, bitwise-unaffected pin) from the fault-injection plane.
#: v4: added rows["mesh_sweep"] (`--mesh-sweep`: wall tokens/s +
#: TTFT/TPOT p50 per device count over host-device meshes, plus one
#: tensor-parallel point; EXPERIMENTS.md §Mesh-sharding).
#: v5: added rows["overlap"] (`--overlap-sweep`: inline vs overlapped
#: serve tokens/s + hit fraction + migrated bytes on the contended
#: stream, plus the cost_aware measured-payback bound_fraction vs the
#: PR 5 modeled baseline; EXPERIMENTS.md §Async-migration).
#: v6: added rows["goodput"] (`--goodput-sweep`: per-policy
#: goodput-under-SLO curves on the workload plane's seeded mixed
#: Poisson+bursty stream — modeled-latency goodput per target scale,
#: live SA bound_fraction, per-arrival-pattern terminal-status and
#: shed counts, TTFT decomposition percentiles, EOS-stop counts;
#: EXPERIMENTS.md §Workloads).
BENCH_SCHEMA_VERSION = 6

#: PR 5 serve-sweep cost_aware aggregate bound_fraction on the ci
#: stream with MODELED payback (the number measured recalibration has
#: to beat; see EXPERIMENTS.md §Async-migration).
PR5_COST_AWARE_BOUND = 0.7271


def _git_commit() -> str:
    """Best-effort producing-commit stamp for BENCH_engine.json."""
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _stamp(result: dict) -> dict:
    """Stamp schema version + producing commit onto a result dict."""
    result["schema_version"] = BENCH_SCHEMA_VERSION
    result["commit"] = _git_commit()
    return result


# --------------------------------------------------------------------------- #
# seed-style host-side control plane (verbatim behavior of the old engine)
# --------------------------------------------------------------------------- #

class HostLoopEngine(ServingEngine):
    """Pre-fusion reference: host control plane, unfused data plane."""

    def step(self, token):
        from repro.serving.engine import _set_cache
        write_slot = self._host_control_plane(self._cache)
        logits, state = self.model.decode_step(
            self.params, self.state, token, write_slot=write_slot)
        self.state = state
        plan, n_pro, n_dem = self._host_plan_migrations(self._cache)
        # read traffic is priced on post-decode, PRE-migration residency,
        # matching the fused engine's accounting
        self._record_host(n_pro, n_dem)
        if plan is not None:
            self.state = _set_cache(
                self.state, apply_migrations(self._cache, plan))
        return logits

    def _host_control_plane(self, cache):
        geo = self.geo
        length = int(np.asarray(cache.length)[0])
        T = geo.page_tokens
        logical = min(length // T, geo.max_pages - 1)
        pt = np.asarray(cache.page_table)
        ho = np.asarray(cache.hbm_owner)
        eo = np.asarray(cache.host_owner)
        L, B = pt.shape[0], pt.shape[1]
        ws = np.zeros((L, B), np.int32)
        for l in range(L):
            for b in range(B):
                if pt[l, b, logical] >= 0:
                    ws[l, b] = pt[l, b, logical]
                else:
                    free_h = np.nonzero(ho[l, b] < 0)[0]
                    if len(free_h):
                        ws[l, b] = free_h[0]
                    else:
                        free_e = np.nonzero(eo[l, b] < 0)[0]
                        ws[l, b] = geo.hbm_pages + (
                            free_e[0] if len(free_e) else geo.host_pages - 1)
        return jnp.asarray(ws)

    def _host_plan_migrations(self, cache):
        imp = np.asarray(cache.importance)
        ho = np.asarray(cache.hbm_owner)
        eo = np.asarray(cache.host_owner)
        L, B = ho.shape[0], ho.shape[1]
        budget = max(1, int(self.cfg.migration_budget_frac
                            * self.geo.hbm_pages))
        promotes, demotes = [], []
        for l in range(L):
            for b in range(B):
                host_pages = np.nonzero(eo[l, b] >= 0)[0]
                if not len(host_pages):
                    continue
                host_logical = eo[l, b, host_pages]
                host_imp = imp[l, b, host_logical]
                order = np.argsort(-host_imp, kind="stable")
                hot = [(host_pages[i], host_logical[i], host_imp[i])
                       for i in order[:budget]
                       if host_imp[i] > self.cfg.promote_thresh]
                if not hot:
                    continue
                hbm_pages = np.nonzero(ho[l, b] >= 0)[0]
                hbm_logical = ho[l, b, hbm_pages]
                hbm_imp = imp[l, b, hbm_logical]
                cold_order = np.argsort(hbm_imp, kind="stable")
                free = np.nonzero(ho[l, b] < 0)[0].tolist()
                ci = 0
                for src, logical, h_imp in hot:
                    if free:
                        dst = free.pop(0)
                    elif ci < len(cold_order):
                        victim = cold_order[ci]
                        if hbm_imp[victim] >= h_imp:
                            break
                        vslot = hbm_pages[victim]
                        demotes.append((l, b, vslot, src,
                                        hbm_logical[victim]))
                        dst = vslot
                        ci += 1
                    else:
                        break
                    promotes.append((l, b, src, dst, logical))
        if not promotes and not demotes:
            return None, 0, 0
        # the step-varying capacity that forced per-step recompiles
        cap = max(len(promotes), len(demotes), 1)
        plan = MigrationPlan.build(cap, promotes, demotes)
        return plan, len(promotes), len(demotes)

    def _record_host(self, n_pro, n_dem):
        cache = self._cache
        h_pages = int(np.asarray((cache.hbm_owner >= 0).sum()))
        e_pages = int(np.asarray((cache.host_owner >= 0).sum()))
        self._record((np.asarray([[h_pages, e_pages, n_pro, n_dem]]),))


# --------------------------------------------------------------------------- #
# PR 2-style eager admission (the serialization chunked prefill removed)
# --------------------------------------------------------------------------- #

class EagerAdmissionEngine(ServingEngine):
    """Eager-admission baseline: admission prefills the WHOLE prompt on
    the spot with a batch-1 `model.forward` (compiling once per
    page-rounded prompt length), binds it via `control.insert_lane`,
    and samples the first token on host — PR 2's retired admission
    path, kept faithful here (like `HostLoopEngine`) so the chunked-
    prefill TTFT win stays measurable PR over PR."""

    def _admit_lane(self, req, hs):
        geo = self.geo
        S = req.prompt_len
        pad = (-S) % geo.page_tokens
        prompt = jnp.asarray(np.asarray(req.prompt),
                             jnp.int32).reshape(1, -1)
        if pad:
            prompt = jnp.pad(prompt, ((0, 0), (0, pad)))
        logits, (k, v) = self.model.forward(self.params, prompt,
                                            collect_kv=True)
        lane_cache = prefill_cache(dataclasses.replace(geo, batch=1),
                                   k, v, S)
        if not hasattr(self, "_insert_jit"):
            self._insert_jit = jax.jit(control.insert_lane,
                                       donate_argnums=(0,))
        lane = req.lane
        self.state = self._insert_jit(self.state, lane_cache,
                                      jnp.int32(lane))
        rkey = jax.random.fold_in(hs["root"], req.rid)
        rkey, sub = jax.random.split(rkey)
        tok0 = int(self._sampler(logits[0, S - 1][None], sub[None])[0])
        req.output.append(tok0)
        req.generated = 1
        req.prefilled = S              # device sees a decode-ready lane
        req.first_token_at = time.time()
        req.phase = "decoding"
        hs["prompt_buf"][lane, :] = 0
        hs["token"][lane] = tok0
        hs["keys"][lane] = np.array(rkey)
        done = (req.generated >= req.max_new_tokens
                or (self.cfg.eos_id is not None
                    and tok0 == self.cfg.eos_id))
        if done:
            mask = np.arange(geo.batch) == lane
            self.state = self._release_jit(self.state, jnp.asarray(mask))
            self.batcher.complete(req)     # lane -> -1: serve() skips it


# --------------------------------------------------------------------------- #

def _engine(model, params, policy, klass=ServingEngine, batch=2):
    eng = klass(model, params, EngineConfig(
        max_context=512, hbm_fraction=0.25, policy=policy,
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=STRIDE))
    rng = np.random.default_rng(0)
    # the prompt spills past the HBM pool so migrations actually fire
    prompts = jnp.asarray(rng.integers(0, model.cfg.vocab, (batch, 272)),
                          jnp.int32)
    eng.start(prompts)
    return eng


def _time_steps(eng, steps):
    tok = jnp.array([1, 2], jnp.int32)
    tok = jnp.argmax(eng.step(tok), -1).astype(jnp.int32)   # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        tok = jnp.argmax(eng.step(tok), -1).astype(jnp.int32)
    jax.block_until_ready(tok)
    return steps / (time.perf_counter() - t0)


def _time_fused(eng, steps):
    eng.generate(jnp.array([1, 2], jnp.int32), STRIDE)      # compile
    tok = jnp.array([3, 4], jnp.int32)
    t0 = time.perf_counter()
    out = eng.generate(tok, steps)
    jax.block_until_ready(out)
    return steps / (time.perf_counter() - t0)


def _time_serve(model, params, *, stride, max_context, n_requests=6):
    """Mixed-length request stream through `serve`; prompts span three
    distinct page-rounded lengths (2/3/4 pages), which under eager
    admission cost three separate prefill compiles — the chunked loop
    must hold ONE serve-chunk executable across the whole stream.
    Returns (tokens/s, serve-chunk executable count, ServeReport)."""
    eng = ServingEngine(model, params, EngineConfig(
        max_context=max_context, hbm_fraction=0.25, policy="importance",
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=stride, prefill_chunk=16))
    rng = np.random.default_rng(0)
    def mk():
        return [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab,
                                            (32 + 16 * (i % 3),)),
                        max_new_tokens=stride // 2 + 4 * (i % 3))
                for i in range(n_requests)]
    eng.serve(mk(), num_slots=2, seed=0)                    # compile
    reqs = mk()
    t0 = time.perf_counter()
    report = eng.serve(reqs, num_slots=2, seed=1)
    total = sum(len(r.output) for r in report)
    return total / (time.perf_counter() - t0), \
        eng._serve_jit._cache_size(), report


def _ttft_long_prompt(model, params, klass, *, stride, max_context,
                      long_len):
    """TTFT of a long prompt admitted MID-STREAM behind short requests.

    The warmup stream covers the short lengths only, so the timed
    stream's long prompt arrives with a fresh page-rounded length —
    under eager admission that is a blocking compile + whole-prompt
    forward at the admission boundary; under chunked prefill it is just
    more slices through the already-compiled mixed-step executable.
    Returns the long request's TTFT in seconds."""
    eng = klass(model, params, EngineConfig(
        max_context=max_context, hbm_fraction=0.25, policy="importance",
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=stride, prefill_chunk=16))
    rng = np.random.default_rng(1)

    def mk(with_long):
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab,
                                            (32 + 16 * (i % 2),)),
                        max_new_tokens=stride + 2)
                for i in range(4)]
        if with_long:
            reqs.append(Request(
                rid=99, prompt=rng.integers(0, model.cfg.vocab,
                                            (long_len,)),
                max_new_tokens=4))
        return reqs

    eng.serve(mk(False), num_slots=2, seed=0)               # warmup
    report = eng.serve(mk(True), num_slots=2, seed=1)
    long_req = next(r for r in report if r.rid == 99)
    assert long_req.started_step > 0, "long prompt was not mid-stream"
    return long_req.first_token_at - long_req.submitted_at


def _policy_sweep(model, params, *, steps, ci):
    """Every registered device policy over the same fused generate
    stream, scored live against the simulator bounds (see module doc).

    The stream decodes batch 1 with a prompt that spills past the HBM
    pool and Quest sparsity 0.5, so placement actually matters: the
    read set concentrates on the top-importance pages and a policy
    that promotes them converts host reads into HBM hits. Returns
    {policy: {steps_per_s, hit_fraction, bound_fraction, ...}}.
    """
    sa_cfg = SAConfig(max_evaluations=12 if ci else 40,
                      iters_per_level=4 if ci else 10, seed=0)
    # fused generate compiles once per DISTINCT chunk length; round up
    # so a ragged tail chunk can't trip the one-executable assert on a
    # legitimate --steps value
    steps = -(-steps // STRIDE) * STRIDE
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, model.cfg.vocab, (1, 272)),
                          jnp.int32)
    sweep = {}
    for name in policy_names():
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy=name,
            attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
            telemetry_stride=STRIDE, trace_telemetry=True))
        eng.start(prompts)
        eng.generate(jnp.array([1], jnp.int32), STRIDE)     # compile
        eng.start(prompts)                                  # fresh stream
        t0 = time.perf_counter()
        out = eng.generate(jnp.array([1], jnp.int32), steps)
        jax.block_until_ready(out)
        sps = steps / (time.perf_counter() - t0)
        # one executable per policy: policy-state values change every
        # step, plan shapes and policy code never do
        exes = eng._gen_jit._cache_size()
        assert exes == 1, (name, exes)
        rec = trace_bridge.collect(eng)
        score = trace_bridge.score_headroom(rec, GH200, sa_cfg=sa_cfg)
        sweep[name] = {
            "steps_per_s": sps,
            "hit_fraction": score["live_hit_fraction"],
            "bound_fraction": score["bound_fraction"],
            "headroom_vs_static": score["headroom_vs_static"],
            "live_total_s": score["live_total_s"],
            "sa_total_s": score["sa_total_s"],
            "belady_total_s": score["belady_total_s"],
            "static_total_s": score["static_total_s"],
            "gen_executables": exes,
        }
    if ci:
        # the whole point of dynamic placement, gated: the deployable
        # policy must convert masked reads into HBM hits vs never
        # migrating (equality allowed — a capacity-bound degenerate
        # geometry can't be beaten)
        assert sweep["importance"]["hit_fraction"] >= \
            sweep["static"]["hit_fraction"], sweep
    return sweep


def _serve_policy_sweep(model, params, *, ci):
    """Every registered device policy over the SAME mixed
    continuous-batching serve stream, with per-request attribution
    (see module doc / EXPERIMENTS.md §Serve-trace).

    The stream's 272/288-token prompts spill past the 16-page per-lane
    HBM pool (ctx 512) and Quest sparsity concentrates the decode read
    set, so placement matters under lane churn: requests are admitted,
    complete, and hand lanes to queued successors while the capture
    runs. Returns {policy: {aggregate: {...}, requests: {rid: {...}},
    serve_executables}}.
    """
    sa_cfg = SAConfig(max_evaluations=8 if ci else 24,
                      iters_per_level=3 if ci else 8, seed=0)
    rng = np.random.default_rng(0)
    n_requests = 3 if ci else 6
    prompts = [rng.integers(0, model.cfg.vocab, (272 + 16 * (i % 2),))
               for i in range(n_requests)]

    def mk():
        return [Request(rid=i, prompt=p, max_new_tokens=6 + 2 * (i % 2))
                for i, p in enumerate(prompts)]

    sweep = {}
    for name in policy_names():
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy=name,
            attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
            telemetry_stride=8, prefill_chunk=16,
            trace_telemetry=True))
        report = eng.serve(mk(), num_slots=2, seed=0)
        # serve telemetry adds ZERO retraces: one mixed-step executable
        # per policy, capture on, across admission/reclaim/lane reuse
        exes = eng._serve_jit._cache_size()
        assert exes == 1, (name, exes)
        rec = trace_bridge.collect_serve(eng)
        score = trace_bridge.score_serve(rec, GH200, sa_cfg=sa_cfg,
                                         report=report)
        sweep[name] = {
            "aggregate": score["aggregate"],
            "requests": {str(rid): sc
                         for rid, sc in score["requests"].items()},
            "serve_executables": exes,
        }
        if ci:
            agg = score["aggregate"]
            assert agg["live_total_s"] > 0 and "bound_fraction" in agg, \
                (name, agg)
            assert len(score["requests"]) == n_requests, (name, score)
            for sc in score["requests"].values():
                assert {"hit_fraction", "bound_fraction"} <= set(sc), sc
    return sweep


def _overlap_sweep(model, params, *, ci):
    """Inline vs overlapped serve on the contended mixed stream
    (module doc leg ten / EXPERIMENTS.md §Async-migration).

    Same stream shape as `_serve_policy_sweep`: 272/288-token prompts
    spill the 16-page per-lane HBM pool (ctx 512) and Quest sparsity
    0.5 concentrates the decode read set, so the pipeline actually
    stages, revalidates, and commits plans while decode runs. The
    importance policy drives both modes; a third leg reruns cost_aware
    with `measured_payback` to price promotion paybacks off the
    measured link instead of the modeled one.

    CI gates: overlapped tokens/s >= 0.9x inline (the split must never
    COST wall-clock; CPU host devices serialize the copy with compute,
    so the real overlap win is not measurable here and the gate is a
    no-regression bound), hit fractions equal within +-0.01 (one step
    of staging lag must not change where reads land), one executable
    per mode, and the measured-payback cost_aware bound_fraction at
    least the PR 5 modeled baseline.
    """
    sa_cfg = SAConfig(max_evaluations=8 if ci else 24,
                      iters_per_level=3 if ci else 8, seed=0)
    rng = np.random.default_rng(0)
    n_requests = 3 if ci else 4
    prompts = [rng.integers(0, model.cfg.vocab, (272 + 16 * (i % 2),))
               for i in range(n_requests)]

    # decodes are LONG (~50 steps) on purpose: the pipeline's one step
    # of staging lag costs one extra host-read step per promotion, a
    # transient that the +-0.01 hit-fraction gate can only absorb once
    # the steady state dominates the stream
    def mk():
        return [Request(rid=i, prompt=p,
                        max_new_tokens=48 + 4 * (i % 2))
                for i, p in enumerate(prompts)]

    def run_mode(policy, overlap, measured=False):
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy=policy,
            attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
            telemetry_stride=8, prefill_chunk=16, trace_telemetry=True,
            overlap_migrations=overlap, measured_payback=measured))
        eng.serve(mk(), num_slots=2, seed=0)                # compile
        t0 = time.perf_counter()
        report = eng.serve(mk(), num_slots=2, seed=0)
        wall = time.perf_counter() - t0
        exes = eng._serve_jit._cache_size()
        assert exes == 1, (policy, overlap, exes)
        rec = trace_bridge.collect_serve(eng)
        score = trace_bridge.score_serve(rec, GH200, sa_cfg=sa_cfg,
                                         report=report)
        agg = score["aggregate"]
        total = sum(len(r.output) for r in report)
        row = {
            "tokens_per_s": total / wall,
            "hit_fraction": agg["live_hit_fraction"],
            "bound_fraction": agg["bound_fraction"],
            "migrated_bytes": int(sum(s.m_in + s.m_out
                                      for s in eng.stats)),
            "serve_chunk_executables": exes,
        }
        if measured:
            row["payback_events"] = [
                e for e in report.events
                if e["kind"] == "payback_measured"]
        return row

    sweep = {
        "inline": run_mode("importance", overlap=False),
        "overlap": run_mode("importance", overlap=True),
        "cost_aware_measured": run_mode("cost_aware", overlap=True,
                                        measured=True),
        "pr5_cost_aware_bound_baseline": PR5_COST_AWARE_BOUND,
    }
    if ci:
        inline, over = sweep["inline"], sweep["overlap"]
        assert over["tokens_per_s"] >= 0.9 * inline["tokens_per_s"], \
            (f"overlap regressed below inline: "
             f"{over['tokens_per_s']:.1f} < {inline['tokens_per_s']:.1f}"
             f" tokens/s")
        assert abs(over["hit_fraction"] - inline["hit_fraction"]) \
            <= 0.01, (over["hit_fraction"], inline["hit_fraction"])
        # one step of lag + hazard masking loses at most a trickle of
        # commits; the pipeline must still MOVE pages
        assert over["migrated_bytes"] > 0, over
        ca = sweep["cost_aware_measured"]
        assert ca["payback_events"], "measured payback never measured"
        assert ca["bound_fraction"] >= PR5_COST_AWARE_BOUND, \
            (ca["bound_fraction"], PR5_COST_AWARE_BOUND)
    return sweep


def _assert_serve_bridge_matches_generate(model, params):
    """CI pin: a single-request serve stream's stitched trace is
    BITWISE the generate bridge's record (same access pattern, same
    read-time placement, same prompt arithmetic) — the serve capture
    is the same instrument pointed at the same program."""
    rng = np.random.default_rng(11)
    S, n = 32, 7
    prompt = rng.integers(0, model.cfg.vocab, (S,))
    cfg = EngineConfig(max_context=128, hbm_fraction=0.25,
                       policy="importance", attention_sparsity=0.0,
                       spec=GH200, promote_thresh=1e-4,
                       telemetry_stride=4, prefill_chunk=16,
                       trace_telemetry=True)
    ref = ServingEngine(model, params, cfg)
    logits0 = ref.start(jnp.asarray(prompt[None], jnp.int32))
    tok0 = jnp.argmax(logits0, -1).astype(jnp.int32)
    ref.generate(tok0, n - 1)
    grec = trace_bridge.collect(ref)

    eng = ServingEngine(model, params, cfg)
    eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=n)],
              num_slots=1)
    atts = trace_bridge.attribute(trace_bridge.collect_serve(eng))
    rec = atts[0].record
    assert np.array_equal(rec.access, grec.access)
    assert np.array_equal(rec.tier, grec.tier)
    assert rec.prompt_len == grec.prompt_len


def _chaos_smoke(model, params):
    """Graceful-degradation smoke (module doc leg eight): same engine,
    same stream, clean then under a seeded four-kind fault schedule.
    Returns the BENCH row; raises AssertionError on any contract break.
    """
    eng = ServingEngine(model, params, EngineConfig(
        max_context=128, hbm_fraction=0.25, policy="cost_aware",
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=8, prefill_chunk=16))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, model.cfg.vocab, (24 + 8 * (i % 3),))
               for i in range(4)]

    def mk():
        return [Request(rid=i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]

    clean = eng.serve(mk(), num_slots=2, seed=0)
    assert all(r.status == "ok" for r in clean), clean.statuses
    clean_out = {r.rid: list(r.output) for r in clean}

    plane = FaultPlane(
        tier=(TierFault(start=4, stop=20, link_scale=0.05),),
        migration=(MigrationFault(start=0, stop=12, commit_frac=0.0),),
        pool=(PoolFault(step=16, delta=-2), PoolFault(step=32, delta=2)),
        poison=(PoisonFault(rid=1, step=6),))
    report = eng.serve(mk(), num_slots=2, seed=0, faults=plane)

    statuses = report.statuses
    assert set(statuses) == set(clean_out), statuses
    assert all(s in TERMINAL_STATUSES for s in statuses.values()), \
        statuses
    assert statuses[1] == "failed", statuses
    for r in report:
        if r.rid != 1:       # fault-free lanes: bitwise identical
            assert r.status == "ok" and list(r.output) == \
                clean_out[r.rid], (r.rid, r.status)
    # faults are data, not shape: clean + faulted share ONE executable
    exes = eng._serve_jit._cache_size()
    assert exes == 1, exes
    assert report.events, "fault schedule produced no telemetry events"
    n_ok = sum(1 for s in statuses.values() if s == "ok")
    return {
        "statuses": {str(k): v for k, v in sorted(statuses.items())},
        "ok_requests": n_ok,
        "failed_requests": len(statuses) - n_ok,
        "fault_events": len(report.events),
        "serve_chunk_executables": exes,
    }


def _mesh_point(model, params, mesh, *, num_slots, ci):
    """One scaling point: the mixed serve stream on `mesh` (None = the
    single-device baseline). Returns the BENCH row for this point."""
    stride = 8
    eng = ServingEngine(model, params, EngineConfig(
        max_context=128, hbm_fraction=0.25, policy="importance",
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=stride, prefill_chunk=16), mesh=mesh)
    rng = np.random.default_rng(0)
    n_requests = 2 * num_slots if ci else 3 * num_slots

    def mk():
        return [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab,
                                            (32 + 16 * (i % 3),)),
                        max_new_tokens=stride // 2 + 2 * (i % 3))
                for i in range(n_requests)]

    eng.serve(mk(), num_slots=num_slots, seed=0)            # compile
    reqs = mk()
    t0 = time.perf_counter()
    report = eng.serve(reqs, num_slots=num_slots, seed=1)
    wall = time.perf_counter() - t0
    exes = eng._serve_jit._cache_size()
    if ci:
        # the scaling gate is STRUCTURAL, not a speedup assertion:
        # forced host devices share the same physical cores, so the
        # curve's shape is honest data, not a pass/fail criterion
        assert exes == 1, (mesh, exes)
        assert all(s == "ok" for s in report.statuses.values()), \
            report.statuses
    total = sum(len(r.output) for r in report)
    return {
        "devices": 1 if mesh is None else mesh.devices.size,
        "mesh": None if mesh is None else dict(mesh.shape),
        "num_slots": num_slots,
        "requests": n_requests,
        "wall_tokens_per_s": total / wall,
        "ttft_p50_s": report.ttft.get("p50"),
        "tpot_p50_s": report.tpot.get("p50"),
        "serve_chunk_executables": exes,
    }


def _mesh_sweep(model, params, *, ci):
    """tokens/s + TTFT/TPOT vs device count over host-device meshes.

    Sweeps pure data-parallel meshes (data=n, model=1) for every
    available power-of-two device count (lanes scale with devices so
    per-device work is constant), plus one tensor-parallel point
    (data=n/2, model=2) at the largest count — the kv_heads/pages
    sharding path. On a 1-device host this degenerates to the baseline
    point, so `--mesh-sweep` runs anywhere; the CI mesh leg forces 8
    host devices for the real curve."""
    from repro.launch.mesh import make_test_mesh
    counts = [n for n in (1, 2, 4, 8) if n <= jax.device_count()]
    points = {}
    for n in counts:
        mesh = None if n == 1 else make_test_mesh(data=n, model=1)
        points[f"{n}x1"] = _mesh_point(model, params, mesh,
                                       num_slots=2 * n, ci=ci)
    top = max(counts)
    if top >= 4:
        points[f"{top // 2}x2"] = _mesh_point(
            model, params, make_test_mesh(data=top // 2, model=2),
            num_slots=top, ci=ci)
    return {"devices_available": jax.device_count(), "points": points}


def run_mesh_sweep(print_csv: bool = True, ci: bool = False):
    """Standalone `--mesh-sweep`: the scaling curve only, appended into
    an existing BENCH_engine.json when present (the CI mesh leg runs
    this under --xla_force_host_platform_device_count=8 and uploads the
    merged artifact)."""
    cfg = configs.get_smoke("internlm2-1.8b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    sweep = _mesh_sweep(model, params, ci=ci)
    try:
        with open("BENCH_engine.json") as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"rows": {}}
    result.setdefault("rows", {})["mesh_sweep"] = sweep
    with open("BENCH_engine.json", "w") as f:
        json.dump(_stamp(result), f, indent=2)
    if print_csv:
        for label, row in sweep["points"].items():
            print(f"mesh/{label}/wall_tokens_per_s,"
                  f"{1e6 / row['wall_tokens_per_s']:.3f},"
                  f"{row['wall_tokens_per_s']:.3f}")
            if row["ttft_p50_s"] is not None:
                print(f"mesh/{label}/ttft_p50,"
                      f"{row['ttft_p50_s'] * 1e6:.3f},"
                      f"{row['ttft_p50_s']:.6f}")
    return sweep


def _goodput_sweep(model, params, *, ci):
    """Workload-plane goodput leg (module doc leg eleven /
    EXPERIMENTS.md §Workloads).

    Traffic comes from `benchmarks/workloads.py`: seeded heavy-tailed
    prompts around the contended 272-token band (spilling the 16-page
    per-lane HBM pool at ctx 512, Quest sparsity 0.5 — the geometry
    where placement matters), priority tiers, and sampled
    (temperature 0.7) decoding that stops on the model's real
    `eos_id`. Per policy: the three single-pattern open-loop streams
    (Poisson / bursty / diurnal) run through the SAME engine first —
    arrivals are pure data, so the serve executable count must stay at
    ONE across all of them — then the mixed Poisson+bursty stream is
    served with SLO-aware admission at a compressed arrival clock
    (every arrival lands before the first chunk completes, making
    admission order and the scored traces deterministic across hosts
    while the open-loop driver still runs) and scored into the
    goodput-under-SLO curve on MODELED per-request latency. The TPOT
    target is the static stream's modeled median, so both policies
    face the same contract and scale 1.0 sits exactly at static's
    half-good point.
    """
    import workloads as wl

    sa_cfg = SAConfig(max_evaluations=8 if ci else 24,
                      iters_per_level=3 if ci else 8, seed=0)
    n_pat = 3 if ci else 5
    n_mixed = 6 if ci else 12
    base = dict(rate_rps=8.0, len_mu=5.6, len_sigma=0.08,
                zipf_frac=0.1, min_prompt=192, max_prompt=288,
                page_tokens=16, snap_frac=0.5, out_mu=2.0,
                out_sigma=0.4, max_new=10, vocab=model.cfg.vocab,
                temperature=0.7)
    scales = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0)
    patterns = ("poisson", "bursty", "diurnal")
    # live-admission contract: generous wall targets (tight targets
    # are exercised by tests/test_slo.py; the bench streams should
    # complete, so shed counts here are descriptive, normally zero)
    admission = SLOPolicy.uniform(ttft_s=300.0, tpot_s=60.0)

    def mk_engine(policy):
        return ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy=policy,
            attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
            telemetry_stride=8, prefill_chunk=16, prefill_budget=24,
            eos_id=model.cfg.eos_id, trace_telemetry=True))

    sweep = {"patterns": list(patterns), "scales": list(scales),
             "latency": "modeled", "policies": {}}
    tpot_target = None
    for policy in ("static", "importance"):
        eng = mk_engine(policy)
        pat_rows = {}
        for i, pat in enumerate(patterns):
            stream = wl.generate(wl.WorkloadSpec(
                seed=11 + i, n_requests=n_pat, arrival=pat, **base))
            rep = wl.drive(eng, stream, num_slots=2, slo=admission)
            statuses = list(rep.statuses.values())
            assert all(s in TERMINAL_STATUSES for s in statuses), rep
            pat_rows[pat] = {
                "requests": len(statuses),
                "ok": statuses.count("ok"),
                "shed": sum(1 for r in rep.rejected
                            if r.error is not None
                            and r.error.code == "slo_shed"),
                "eos_stops": rep.eos.get("eos_stops", 0),
            }
        mixed = wl.mixed_stream(101, n_mixed, **base)
        rep = wl.drive(eng, mixed, num_slots=2, slo=admission,
                       time_scale=1e-3)
        assert all(s in TERMINAL_STATUSES
                   for s in rep.statuses.values()), rep
        execs = int(eng._serve_jit._cache_size())
        rec = trace_bridge.collect_serve(eng)
        if tpot_target is None:
            scored = trace_bridge.score_serve(rec, GH200,
                                              sa_cfg=sa_cfg)
            tpots = sorted(sc["live_total_s"] / sc["steps"]
                           for sc in scored["requests"].values()
                           if sc["steps"])
            tpot_target = float(tpots[len(tpots) // 2])
        contract = SLOPolicy.uniform(ttft_s=300.0, tpot_s=tpot_target)
        out = trace_bridge.goodput_curve(rec, GH200, rep, contract,
                                         scales=scales, sa_cfg=sa_cfg)
        curve = out["curve"]
        sweep["policies"][policy] = {
            "curve": curve,
            "mean_goodput": float(np.mean([c["goodput"]
                                           for c in curve])),
            "bound_fraction": out["aggregate"].get("bound_fraction"),
            "live_hit_fraction": out["aggregate"]["live_hit_fraction"],
            "serve_executables": execs,
            "ttft_parts": rep.ttft_parts,
            "eos": rep.eos,
            "arrival_patterns": pat_rows,
        }
    sweep["tpot_target_s"] = tpot_target
    if ci:
        for policy, row in sweep["policies"].items():
            # one executable across poisson + bursty + diurnal + mixed:
            # arrival patterns are data, never shapes
            assert row["serve_executables"] == 1, \
                (policy, row["serve_executables"])
        st = sweep["policies"]["static"]["mean_goodput"]
        imp = sweep["policies"]["importance"]["mean_goodput"]
        # the deployable policy converts placement headroom into
        # goodput at equal targets (equality allowed: a degenerate
        # geometry no policy can beat)
        assert imp >= st, (imp, st)
    return sweep


def run_goodput_sweep(print_csv: bool = True, ci: bool = False):
    """Standalone `--goodput-sweep`: the workload-plane goodput leg
    only, appended into an existing BENCH_engine.json when present
    (the CI bench-smoke goodput step runs this and uploads the merged
    artifact)."""
    cfg = configs.get_smoke("internlm2-1.8b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    sweep = _goodput_sweep(model, params, ci=ci)
    try:
        with open("BENCH_engine.json") as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"rows": {}}
    result.setdefault("rows", {})["goodput"] = sweep
    with open("BENCH_engine.json", "w") as f:
        json.dump(_stamp(result), f, indent=2)
    if print_csv:
        for policy, row in sweep["policies"].items():
            print(f"goodput/{policy}/mean_goodput,0.000,"
                  f"{row['mean_goodput']:.3f}")
            bf = row["bound_fraction"]
            if bf is not None:
                print(f"goodput/{policy}/bound_fraction,0.000,"
                      f"{bf:.3f}")
    return sweep


def run(print_csv: bool = True, steps: int = STEPS, ci: bool = False):
    cfg = configs.get_smoke("internlm2-1.8b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    host_steps = 2 if ci else HOST_STEPS
    if ci:                     # reduced geometry for the CI smoke job
        steps = min(steps, 2 * STRIDE)

    result = {"steps": steps, "stride": STRIDE, "ci": ci, "rows": {}}
    # rows produced only by the standalone --mesh-sweep/--goodput-sweep
    # legs survive a default rerun, so the committed artifact keeps its
    # scaling curve and goodput curves
    try:
        with open("BENCH_engine.json") as f:
            prior = json.load(f).get("rows", {})
        for standalone in ("mesh_sweep", "goodput"):
            if standalone in prior:
                result["rows"][standalone] = prior[standalone]
    except (OSError, ValueError):
        pass
    rows = []
    for policy in ("static", "importance"):
        host_sps = _time_steps(
            _engine(model, params, policy, HostLoopEngine), host_steps)
        eager_eng = _engine(model, params, policy)
        eager_sps = _time_steps(eager_eng, steps)
        fused_eng = _engine(model, params, policy)
        fused_sps = _time_fused(fused_eng, steps)
        # zero migration-driven retraces: one executable for the eager
        # step, one per distinct scan length for the fused loop
        assert eager_eng._step_jit._cache_size() == 1, \
            eager_eng._step_jit._cache_size()
        assert fused_eng._gen_jit._cache_size() == 1, \
            fused_eng._gen_jit._cache_size()
        if ci:
            # wall-clock gate with a noise margin: shared CI runners
            # jitter single-digit percents; a real fusion regression
            # (lost scan, per-step dispatch) costs far more than 10%
            assert fused_sps >= 0.9 * eager_sps, \
                (f"fused regressed below eager: "
                 f"{fused_sps:.1f} < {eager_sps:.1f} steps/s")
        result["rows"][policy] = {
            "host_steps_per_s": host_sps,
            "eager_steps_per_s": eager_sps,
            "fused_steps_per_s": fused_sps,
            "fused_speedup_vs_host": fused_sps / host_sps,
            "fused_speedup_vs_eager": fused_sps / eager_sps,
            "eager_step_executables": eager_eng._step_jit._cache_size(),
            "fused_gen_executables": fused_eng._gen_jit._cache_size(),
        }
        for mode, sps in (("host", host_sps), ("eager", eager_sps),
                          ("fused", fused_sps)):
            rows.append((f"perf/{policy}/{mode}", 1e6 / sps, sps))
        rows.append((f"perf/{policy}/fused_vs_host", 0.0,
                     fused_sps / host_sps))

    serve_stride = 8 if ci else STRIDE
    serve_ctx = 128 if ci else 512
    serve_tps, serve_exes, report = _time_serve(
        model, params, stride=serve_stride, max_context=serve_ctx,
        n_requests=4 if ci else 6)
    # zero retraces across a stream spanning >= 3 page-rounded prompt
    # lengths: ONE mixed prefill+decode executable, admissions included
    assert serve_exes == 1, serve_exes
    ttft_chunked = _ttft_long_prompt(
        model, params, ServingEngine, stride=serve_stride,
        max_context=serve_ctx, long_len=96)
    ttft_eager = _ttft_long_prompt(
        model, params, EagerAdmissionEngine, stride=serve_stride,
        max_context=serve_ctx, long_len=96)
    if ci:
        # the fresh-length admission compile + blocking forward makes
        # this a wide margin; a chunked-prefill regression (per-length
        # retrace, serialized admission) would erase it
        assert ttft_chunked < ttft_eager, (ttft_chunked, ttft_eager)
    result["rows"]["serve"] = {
        "tokens_per_s": serve_tps,
        "serve_chunk_executables": serve_exes,
        "ttft_s": report.ttft,
        "tpot_s": report.tpot,
        "ttft_long_midstream_chunked_s": ttft_chunked,
        "ttft_long_midstream_eager_s": ttft_eager,
    }
    rows.append(("perf/serve/stream", 1e6 / serve_tps, serve_tps))
    if report.ttft:
        rows.append(("perf/serve/ttft_p50", report.ttft["p50"] * 1e6,
                     report.ttft["p50"]))
        rows.append(("perf/serve/ttft_p95", report.ttft["p95"] * 1e6,
                     report.ttft["p95"]))
    if report.tpot:
        rows.append(("perf/serve/tpot_p50", report.tpot["p50"] * 1e6,
                     report.tpot["p50"]))
        rows.append(("perf/serve/tpot_p95", report.tpot["p95"] * 1e6,
                     report.tpot["p95"]))
    rows.append(("perf/serve/ttft_long_chunked", ttft_chunked * 1e6,
                 ttft_chunked))
    rows.append(("perf/serve/ttft_long_eager", ttft_eager * 1e6,
                 ttft_eager))

    sweep = _policy_sweep(model, params, steps=2 * STRIDE if ci else steps,
                          ci=ci)
    result["rows"]["policy_sweep"] = sweep
    for name, row in sweep.items():
        rows.append((f"policy/{name}/steps_per_s",
                     1e6 / row["steps_per_s"], row["steps_per_s"]))
        rows.append((f"policy/{name}/hit_fraction", 0.0,
                     row["hit_fraction"]))
        rows.append((f"policy/{name}/bound_fraction", 0.0,
                     row["bound_fraction"]))

    if ci:
        _assert_serve_bridge_matches_generate(model, params)
    chaos = _chaos_smoke(model, params)
    result["rows"]["chaos"] = chaos
    rows.append(("chaos/ok_requests", 0.0, chaos["ok_requests"]))
    rows.append(("chaos/failed_requests", 0.0,
                 chaos["failed_requests"]))
    rows.append(("chaos/fault_events", 0.0, chaos["fault_events"]))
    serve_sweep = _serve_policy_sweep(model, params, ci=ci)
    result["rows"]["serve_policy_sweep"] = serve_sweep
    for name, row in serve_sweep.items():
        agg = row["aggregate"]
        rows.append((f"serve_policy/{name}/hit_fraction", 0.0,
                     agg["live_hit_fraction"]))
        rows.append((f"serve_policy/{name}/bound_fraction", 0.0,
                     agg.get("bound_fraction", 0.0)))
    overlap = _overlap_sweep(model, params, ci=ci)
    result["rows"]["overlap"] = overlap
    for mode in ("inline", "overlap", "cost_aware_measured"):
        row = overlap[mode]
        rows.append((f"overlap/{mode}/tokens_per_s",
                     1e6 / row["tokens_per_s"], row["tokens_per_s"]))
        rows.append((f"overlap/{mode}/hit_fraction", 0.0,
                     row["hit_fraction"]))
    rows.append(("overlap/cost_aware_measured/bound_fraction", 0.0,
                 overlap["cost_aware_measured"]["bound_fraction"]))

    with open("BENCH_engine.json", "w") as f:
        json.dump(_stamp(result), f, indent=2)
    if print_csv:
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived:.3f}")
    return result


def run_overlap_sweep(print_csv: bool = True, ci: bool = False):
    """Standalone `--overlap-sweep`: the inline-vs-overlap comparison
    only, appended into an existing BENCH_engine.json when present."""
    cfg = configs.get_smoke("internlm2-1.8b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    sweep = _overlap_sweep(model, params, ci=ci)
    try:
        with open("BENCH_engine.json") as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"rows": {}}
    result.setdefault("rows", {})["overlap"] = sweep
    with open("BENCH_engine.json", "w") as f:
        json.dump(_stamp(result), f, indent=2)
    if print_csv:
        for mode in ("inline", "overlap", "cost_aware_measured"):
            row = sweep[mode]
            print(f"overlap/{mode}/tokens_per_s,"
                  f"{1e6 / row['tokens_per_s']:.3f},"
                  f"{row['tokens_per_s']:.3f}")
            print(f"overlap/{mode}/hit_fraction,0.000,"
                  f"{row['hit_fraction']:.3f}")
            print(f"overlap/{mode}/migrated_bytes,0.000,"
                  f"{row['migrated_bytes']}")
        print(f"overlap/cost_aware_measured/bound_fraction,0.000,"
              f"{sweep['cost_aware_measured']['bound_fraction']:.4f}")
    return sweep


def run_policy_sweep(print_csv: bool = True, steps: int = STEPS):
    """Standalone `--policy-sweep`: the policy plane only — generate
    streams AND the serve-stream sweep with per-request attribution —
    full geometry, appended into an existing BENCH_engine.json when
    present."""
    cfg = configs.get_smoke("internlm2-1.8b")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    sweep = _policy_sweep(model, params, steps=steps, ci=False)
    serve_sweep = _serve_policy_sweep(model, params, ci=False)
    try:
        with open("BENCH_engine.json") as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"rows": {}}
    result.setdefault("rows", {})["policy_sweep"] = sweep
    result["rows"]["serve_policy_sweep"] = serve_sweep
    with open("BENCH_engine.json", "w") as f:
        json.dump(_stamp(result), f, indent=2)
    if print_csv:
        for name, row in sweep.items():
            print(f"policy/{name}/steps_per_s,"
                  f"{1e6 / row['steps_per_s']:.3f},"
                  f"{row['steps_per_s']:.3f}")
            print(f"policy/{name}/hit_fraction,0.000,"
                  f"{row['hit_fraction']:.3f}")
            print(f"policy/{name}/bound_fraction,0.000,"
                  f"{row['bound_fraction']:.3f}")
        for name, row in serve_sweep.items():
            agg = row["aggregate"]
            print(f"serve_policy/{name}/hit_fraction,0.000,"
                  f"{agg['live_hit_fraction']:.3f}")
            print(f"serve_policy/{name}/bound_fraction,0.000,"
                  f"{agg.get('bound_fraction', 0.0):.3f}")
    return sweep, serve_sweep


def main(argv=None) -> None:
    """Command line: one leg per flag (the full engine benchmark by
    default)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--ci", action="store_true",
                    help="reduced geometry + fused>=eager + policy-sweep "
                         "+ chaos graceful-degradation gates (CI smoke)")
    ap.add_argument("--policy-sweep", action="store_true",
                    help="run only the device-policy sweep (steps/s, hit "
                         "fraction, fraction-of-SA-upper-bound per policy)")
    ap.add_argument("--mesh-sweep", action="store_true",
                    help="run only the mesh scaling sweep (tokens/s + "
                         "TTFT/TPOT per device count; pair with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 for the full curve)")
    ap.add_argument("--overlap-sweep", action="store_true",
                    help="run only the inline-vs-overlap serve "
                         "comparison (tokens/s, hit fraction, migrated "
                         "bytes per mode + the measured-payback "
                         "cost_aware bound fraction)")
    ap.add_argument("--goodput-sweep", action="store_true",
                    help="run only the workload-plane goodput leg "
                         "(per-policy goodput-under-SLO curves on the "
                         "seeded mixed Poisson+bursty stream, one "
                         "executable across arrival patterns)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.goodput_sweep:
        run_goodput_sweep(ci=args.ci)
    elif args.overlap_sweep:
        run_overlap_sweep(ci=args.ci)
    elif args.mesh_sweep:
        run_mesh_sweep(ci=args.ci)
    elif args.policy_sweep:
        run_policy_sweep(steps=args.steps)
    else:
        run(steps=args.steps, ci=args.ci)


if __name__ == "__main__":
    main()
