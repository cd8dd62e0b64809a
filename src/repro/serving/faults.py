"""Deterministic fault-injection plane for the fused serve loop.

The paper's premise is that tier bandwidth is a *runtime variable* —
so a production-shaped engine must keep serving (and keep its headroom
accounting honest) when the memory system misbehaves. This module is
the injection side of that contract: a `FaultPlane` is a **seeded,
static schedule** of adverse events, queried by `ServingEngine.serve`
at every chunk boundary and folded into the fused chunk as *data,
never shape* — the serve executable with the fault channel compiled in
is the SAME executable whether or not any fault fires (the
one-executable and zero-retrace pins hold with injection active,
asserted by tests/test_chaos.py).

Fault taxonomy (all windows/steps are fused serve-step indices, i.e.
`ContinuousBatcher.step_idx` units):

  TierFault       host-tier bandwidth degradation / latency spike: the
                  spec's HBM/link/DRAM bandwidths are scaled inside
                  [start, stop). Feeds (a) the per-step Eq. (1)-(5)
                  pricing of `StepStats` (`latency_model.degraded_spec`)
                  and (b) the cost_aware policy's payback
                  recalibration (`DevicePolicy.recalibrate`, values
                  re-uploaded into the scan-threaded policy state at
                  the boundary). Tokens are unaffected by construction
                  — bandwidth is a pricing input, not a compute input.
  MigrationFault  migration-plan drop / partial-commit inside
                  [start, stop): per step, only the first
                  `ceil(commit_frac * budget)` live promote rows (and
                  their paired demote rows) of the `MigrationPlan`
                  commit (`throttle_plan`, jit-safe). Placement — and
                  therefore telemetry and the bridge's scores —
                  reflects the *committed* moves only. Under
                  `EngineConfig.overlap_migrations` the caps throttle
                  the COMMIT of the one-step-lagged STAGED buffer
                  (post-revalidation), so the chaos contract is
                  identical in both modes: plans exist, capped rows
                  land, the rest evaporate.
  PoolFault       page-pool shrink wave: at `step` the scheduler's
                  pool gains `delta` pages (negative = shrink).
                  Reserved pages stay reserved, so `free_pages` may go
                  negative until completions release them; admission
                  stalls meanwhile and permanently-unfittable queued
                  requests are rejected instead of deadlocking.
  PoisonFault     poisoned logits: from `step` on, request `rid`'s
                  lane has its logits overwritten with NaN. The
                  engine's (always-on) non-finite sampling guard
                  quarantines the lane — no token is emitted from the
                  poisoned step, the request ends `failed`, its pages
                  release through the existing masked
                  `control.release_lanes`, and every other lane keeps
                  serving bitwise-identically.

Determinism contract: a `FaultPlane` is pure data — the schedule
depends only on its constructor arguments (or on `FaultPlane.random`'s
seed), and fault application depends only on the engine step index,
never on wall-clock time or host load. Replaying the same requests,
seed, and plane reproduces the same statuses and the same tokens.

Granularity: tier scales, migration caps, and poison masks are exact
per step (threaded through the scan as per-step arrays); pool deltas
land at the chunk boundary whose window covers their step.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.latency_model import degraded_spec
from repro.core.tiers import MemorySystemSpec
from repro.kvcache.migrate import MigrationPlan

#: sentinel commit cap meaning "no migration fault this step" — larger
#: than any real plan capacity, so `throttle_plan` is an identity.
NO_FAULT_CAP = np.int32(2**30)


@dataclasses.dataclass(frozen=True)
class TierFault:
    """Scale the memory system's bandwidths inside [start, stop)."""

    start: int
    stop: int
    hbm_scale: float = 1.0
    link_scale: float = 1.0
    dram_scale: float = 1.0

    def active(self, step: int) -> bool:
        """Whether this fault window covers `step`."""
        return self.start <= step < self.stop


@dataclasses.dataclass(frozen=True)
class MigrationFault:
    """Drop (commit_frac=0) or partially commit migration plans inside
    [start, stop): per planning step only the first
    `ceil(commit_frac * budget)` live promote rows land."""

    start: int
    stop: int
    commit_frac: float = 0.0

    def active(self, step: int) -> bool:
        """Whether this fault window covers `step`."""
        return self.start <= step < self.stop


@dataclasses.dataclass(frozen=True)
class PoolFault:
    """Resize the scheduler's page pool by `delta` pages at `step`
    (negative = shrink wave; a later positive delta models recovery)."""

    step: int
    delta: int


@dataclasses.dataclass(frozen=True)
class PoisonFault:
    """Overwrite request `rid`'s logits with NaN from `step` on (until
    the engine's sampling guard quarantines the lane)."""

    rid: int
    step: int


@dataclasses.dataclass(frozen=True)
class FaultPlane:
    """A static, deterministic schedule of injected faults (see the
    module docstring for taxonomy + contract). Passed to
    `ServingEngine.serve(..., faults=plane)`; safe to reuse across
    serve calls (it is pure data and never mutated)."""

    tier: Tuple[TierFault, ...] = ()
    migration: Tuple[MigrationFault, ...] = ()
    pool: Tuple[PoolFault, ...] = ()
    poison: Tuple[PoisonFault, ...] = ()

    # ------------------------------------------------------------------ #
    # host-side queries (chunk-boundary cadence)
    # ------------------------------------------------------------------ #
    def scales_at(self, step: int) -> Tuple[float, float, float]:
        """(hbm, link, dram) bandwidth scales active at `step` —
        overlapping windows compose multiplicatively."""
        h = k = d = 1.0
        for f in self.tier:
            if f.active(step):
                h *= f.hbm_scale
                k *= f.link_scale
                d *= f.dram_scale
        return h, k, d

    def spec_at(self, step: int, base: MemorySystemSpec
                ) -> MemorySystemSpec:
        """The (possibly degraded) memory-system spec governing `step`:
        `base` with the active tier-fault scales applied."""
        h, k, d = self.scales_at(step)
        if (h, k, d) == (1.0, 1.0, 1.0):
            return base
        return degraded_spec(base, hbm_scale=h, link_scale=k,
                             dram_scale=d)

    def commit_caps(self, step0: int, stride: int,
                    budget_rows: int) -> np.ndarray:
        """Per-step migration commit caps for the chunk starting at
        `step0`, int32 [stride]: `NO_FAULT_CAP` on fault-free steps,
        else `ceil(commit_frac * budget_rows)` (0 = full drop). The
        worst (smallest) active window wins when windows overlap."""
        caps = np.full((stride,), NO_FAULT_CAP, np.int32)
        for f in self.migration:
            lo = max(f.start - step0, 0)
            hi = min(f.stop - step0, stride)
            if lo < hi:
                cap = int(np.ceil(f.commit_frac * budget_rows))
                caps[lo:hi] = np.minimum(caps[lo:hi], cap)
        return caps

    def pool_delta(self, step0: int, stride: int) -> int:
        """Net page-pool delta of PoolFaults scheduled inside
        [step0, step0 + stride) — applied at that chunk's boundary."""
        return sum(f.delta for f in self.pool
                   if step0 <= f.step < step0 + stride)

    def poison_steps(self, step0: int, stride: int,
                     rids: np.ndarray) -> np.ndarray:
        """Per-step lane poison mask, bool [stride, B]: lane b is
        poisoned at chunk-local step i when a PoisonFault targets its
        bound rid and `fault.step <= step0 + i`. Free lanes (rid -1)
        are never poisoned."""
        mask = np.zeros((stride, len(rids)), bool)
        for f in self.poison:
            lanes = np.nonzero(rids == f.rid)[0]
            if lanes.size:
                lo = max(f.step - step0, 0)
                if lo < stride:
                    mask[lo:, lanes] = True
        return mask

    def window_events(self, step0: int, stride: int) -> list:
        """Schedule entries ACTIVATING inside [step0, step0 + stride),
        as telemetry event dicts — the engine stamps these into
        `ServeReport.events` so a scored stream names the faults that
        shaped its placement."""
        lo, hi = step0, step0 + stride
        out = []
        for f in self.tier:
            if lo <= f.start < hi:
                out.append({"kind": "tier_degradation", "step": f.start,
                            "stop": f.stop, "hbm_scale": f.hbm_scale,
                            "link_scale": f.link_scale,
                            "dram_scale": f.dram_scale})
        for f in self.migration:
            if lo <= f.start < hi:
                out.append({"kind": "migration_fault", "step": f.start,
                            "stop": f.stop,
                            "commit_frac": f.commit_frac})
        for f in self.pool:
            if lo <= f.step < hi:
                out.append({"kind": "pool_resize", "step": f.step,
                            "delta": f.delta})
        for f in self.poison:
            if lo <= f.step < hi:
                out.append({"kind": "logit_poison", "step": f.step,
                            "rid": f.rid})
        return out

    # ------------------------------------------------------------------ #
    @staticmethod
    def random(seed: int, *, steps: int, rids: Sequence[int] = (),
               n_tier: int = 2, n_migration: int = 2, n_pool: int = 1,
               n_poison: int = 1, max_shrink: int = 2) -> "FaultPlane":
        """A seeded random schedule over a `steps`-long stream — the
        chaos-smoke generator. Deterministic: the same (seed, kwargs)
        always builds the identical plane."""
        rng = np.random.default_rng(seed)

        def window():
            a = int(rng.integers(0, max(steps - 1, 1)))
            b = int(rng.integers(a + 1, steps + 1))
            return a, b

        tier = []
        for _ in range(n_tier):
            a, b = window()
            tier.append(TierFault(
                start=a, stop=b,
                link_scale=float(rng.uniform(0.1, 0.8)),
                dram_scale=float(rng.uniform(0.25, 1.0))))
        migration = []
        for _ in range(n_migration):
            a, b = window()
            migration.append(MigrationFault(
                start=a, stop=b,
                commit_frac=float(rng.choice([0.0, 0.5]))))
        pool = [PoolFault(step=int(rng.integers(0, max(steps, 1))),
                          delta=-int(rng.integers(1, max_shrink + 1)))
                for _ in range(n_pool)]
        poison = []
        if rids:
            picks = rng.choice(np.asarray(list(rids)),
                               size=min(n_poison, len(rids)),
                               replace=False)
            poison = [PoisonFault(rid=int(r),
                                  step=int(rng.integers(0, max(steps, 1))))
                      for r in picks]
        return FaultPlane(tier=tuple(tier), migration=tuple(migration),
                          pool=tuple(pool), poison=tuple(poison))


# -------------------------------------------------------------------------- #
# jit-safe plan throttling (the traced half of the migration fault)
# -------------------------------------------------------------------------- #

def throttle_plan(plan: MigrationPlan, cap) -> MigrationPlan:
    """Commit only the first `cap` live promote rows of a plan (and
    their index-paired demote rows); the rest become -1 sentinel no-ops.

    `cap` is a traced int32 scalar — DATA, so a fault-free step
    (cap >= capacity) is a bitwise identity and the executable never
    retraces across fault schedules. Demote rows are masked with the
    SAME row mask as promotes (`plan_by_score` pairs demote i with
    promote i), so a partial commit can never orphan half a swap.

    In overlap mode the engine applies this to the STAGED plan after
    `control.revalidate_plan` masked its hazards — throttling the
    commit, never the planning, so a zero cap (full drop / static
    fallback) still leaves the pipeline staging fresh plans that then
    evaporate, exactly like the inline path's drop semantics."""
    live = plan.pro_layer >= 0
    keep = (jnp.cumsum(live.astype(jnp.int32)) <= cap) & live

    def m(a):
        return jnp.where(keep, a, jnp.int32(-1))

    return MigrationPlan(
        m(plan.pro_layer), m(plan.pro_batch), m(plan.pro_src),
        m(plan.pro_dst), m(plan.pro_logical),
        m(plan.dem_layer), m(plan.dem_batch), m(plan.dem_src),
        m(plan.dem_dst), m(plan.dem_logical))
