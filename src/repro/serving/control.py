"""Device-side (jit-safe) decode control plane.

The paper's premise is that placement decisions happen at token
cadence, so the control plane must be cheap relative to the data plane.
Everything here is statically-shaped JAX vectorized over [L, B] — no
Python loops, no host round-trips — so the whole decode step (write-slot
selection, Quest-style top-k page masking, importance-EMA migration
planning) fuses into one jitted program and can run under `lax.scan`
(see `ServingEngine.run` / `.generate` and EXPERIMENTS.md §Fused-engine).

Semantics, as a per-(layer, batch) loop would state them:

  * write slot: the token's logical page keeps its existing mapping;
    a fresh page takes the first free HBM slot, else the first free
    host slot, else the last host slot.
  * quest mask: keep the top-k pages by importance EMA (k from the
    sparsity target), always keeping the sink page and the two most
    recent pages.
  * migrations: per (layer, batch), promote the `budget` hottest host
    pages above `promote_thresh`; free HBM slots are consumed first
    (in slot order), then the coldest HBM residents are swapped out —
    the i-th hottest candidate displaces the i-th coldest victim only
    if strictly hotter, which reproduces the sequential early-break of
    the loop form (candidate importance is non-increasing in i while
    victim importance is non-decreasing).

Overlap mode (EXPERIMENTS.md §Async-migration) threads a STAGED
`MigrationPlan` through the serve scan carry: step N commits the plan
step N-1 staged while planning for step N+1. The hazard masking that
makes the one-step lag safe lives here — `revalidate_plan` re-checks
every staged row against the commit-time owner maps (in-flight decode /
prefill allocations invalidate rows instead of being clobbered), and
`mask_plan_lanes` drops rows for lanes the host rebound at a chunk
boundary (lane reuse can reproduce identical (slot, logical) pairs for
a different request, which owner maps cannot distinguish).

Under a device mesh (EXPERIMENTS.md §Mesh-sharding) nothing here
changes: planning is elementwise over [L, B] pools that GSPMD shards
lanes-over-`data` and heads/pages-over-`model`, plan tensors inherit
the pool shardings, and the per-boundary commit caps
(`MigrationFault` throttles) stay replicated scalars — so the control
plane partitions along with the data plane with no extra collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kvcache.migrate import MigrationPlan
from repro.kvcache.paged import NO_SLOT, PagedKVCache, no_write_slot


def choose_write_slot(cache: PagedKVCache,
                      active: Optional[jax.Array] = None) -> jax.Array:
    """Physical slot [L, B] receiving this step's token. Lanes outside
    `active` (bool [B]; None = every lane) get NO_WRITE
    (`paged.no_write_slot`), so the decode step writes nothing there."""
    T = cache.k_hbm.shape[3]
    hbm_pages = cache.k_hbm.shape[2]
    host_pages = cache.k_host.shape[2]
    max_pages = cache.page_table.shape[2]
    B = cache.length.shape[0]

    logical = jnp.minimum(cache.length // T, max_pages - 1)        # [B]
    existing = cache.page_table[:, jnp.arange(B), logical]         # [L, B]

    free_h = cache.hbm_owner < 0                                   # [L,B,Ph]
    has_h = jnp.any(free_h, axis=-1)
    first_h = jnp.argmax(free_h, axis=-1).astype(jnp.int32)
    free_e = cache.host_owner < 0
    has_e = jnp.any(free_e, axis=-1)
    first_e = jnp.argmax(free_e, axis=-1).astype(jnp.int32)

    spill = hbm_pages + jnp.where(has_e, first_e, host_pages - 1)
    fresh = jnp.where(has_h, first_h, spill)
    slot = jnp.where(existing >= 0, existing, fresh).astype(jnp.int32)
    if active is None:
        return slot
    return jnp.where(active[None, :], slot, no_write_slot(cache))


def quest_page_mask(cache: PagedKVCache, sparsity: float) -> jax.Array:
    """Quest-style top-k page mask, bool [L, B, max_pages].

    Keeps ceil-rounded (1 - sparsity) * n_alive pages per (layer, batch)
    ranked by importance EMA (at least 1), plus the sink page (logical
    0) and the two most recently born pages.
    """
    alive = cache.page_table >= 0                                  # [L,B,P]
    n_alive = alive.sum(axis=-1)                                   # [L,B]
    k = jnp.maximum(1, jnp.round((1.0 - sparsity)
                                 * n_alive).astype(jnp.int32))
    imp = jnp.where(alive, cache.importance, -jnp.inf)
    order = jnp.argsort(-imp, axis=-1)          # stable desc; dead last
    rank = jnp.argsort(order, axis=-1)          # rank of each page
    topk = rank < k[..., None]
    idx = jnp.arange(alive.shape[-1])[None, None, :]
    sink = idx == 0
    recent = idx >= (n_alive[..., None] - 2)
    return alive & (topk | sink | recent)


def migration_budget(geo, frac: float) -> int:
    """Per-(layer, batch) promote budget — a static Python int, so plan
    capacity (and therefore `apply_migrations`'s traced shapes) depend
    only on the cache geometry, never on step-time page counts."""
    return min(max(1, int(frac * geo.hbm_pages)),
               geo.hbm_pages, geo.host_pages)


def plan_capacity(geo, frac: float) -> int:
    """Fixed MigrationPlan capacity for a geometry: every (layer, batch)
    pair may promote (and thus demote) at most `migration_budget` pages."""
    return geo.num_layers * geo.batch * migration_budget(geo, frac)


def plan_by_score(cache: PagedKVCache, host_score: jax.Array,
                  hbm_score: jax.Array, *, budget: int,
                  promote_thresh, active: Optional[jax.Array] = None,
                  ) -> Tuple[MigrationPlan, jax.Array, jax.Array]:
    """Generic fixed-capacity promote/demote pairing by per-slot score.

    The planner core shared by every device policy (see
    `repro.serving.policies`): per (layer, batch), promote the `budget`
    highest-scoring host slots above `promote_thresh`; free HBM slots
    are consumed first, then the lowest-scoring residents are swapped
    out — the i-th best candidate displaces the i-th worst victim only
    if strictly higher-scoring, reproducing the sequential early-break
    of the loop form.

    host_score [L, B, Pe]: candidate score per host slot. -inf marks
      an ineligible slot (free, or excluded by the policy).
    hbm_score [L, B, Ph]: victim score per HBM slot. -inf marks a free
      slot (always a valid destination); +inf protects a resident from
      eviction (a candidate's finite score can never beat it).
    promote_thresh: float or traced scalar — candidates must exceed it.

    Returns (plan, n_promotes, n_demotes); the plan's capacity is
    L * B * budget regardless of how many rows are live, so
    `apply_migrations` compiles exactly once per geometry.

    `active` (bool [B], optional) gates planning per batch lane: lanes
    whose slot holds no live request (continuous batching) plan no
    moves, so completed/empty lanes never churn pages and their counts
    never pollute the telemetry.
    """
    ho, eo = cache.hbm_owner, cache.host_owner
    L, B, Ph = ho.shape
    Pe = eo.shape[2]
    assert 1 <= budget <= min(Ph, Pe), (budget, Ph, Pe)

    # best `budget` candidate host slots
    cand_imp, cand_slot = jax.lax.top_k(host_score, budget)       # [L,B,M]
    cand_logical = jnp.take_along_axis(eo, cand_slot, axis=-1)

    # destination ranking: free HBM slots (score -inf) first, then the
    # worst residents — ascending stable sort does both at once
    dst_slot = jnp.argsort(hbm_score, axis=-1)[..., :budget].astype(jnp.int32)
    victim_imp = jnp.take_along_axis(hbm_score, dst_slot, axis=-1)
    victim_logical = jnp.take_along_axis(ho, dst_slot, axis=-1)

    promote = (cand_imp > promote_thresh) & (victim_imp < cand_imp)
    if active is not None:
        promote = promote & active[None, :, None]
    demote = promote & (victim_logical >= 0)   # dst was occupied: swap out

    lidx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None, None],
                            promote.shape)
    bidx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :, None],
                            promote.shape)

    def rows(ok, *cols):
        return [jnp.where(ok, c, -1).reshape(-1).astype(jnp.int32)
                for c in cols]

    plan = MigrationPlan(
        # promote: host slot cand_slot -> hbm slot dst_slot
        *rows(promote, lidx, bidx, cand_slot, dst_slot, cand_logical),
        # demote: hbm slot dst_slot -> the host slot vacated by the
        # promotion (cand_slot), carrying the victim's logical page
        *rows(demote, lidx, bidx, dst_slot, cand_slot, victim_logical),
    )
    return plan, promote.sum(), demote.sum()


def _mask_plan_rows(plan: MigrationPlan, keep: jax.Array) -> MigrationPlan:
    """Sentinel out every plan row where `keep` is False — BOTH halves
    with the same [M] mask (`plan_by_score` pairs demote i with promote
    i, and a demote row is live only when its promote is), so a masked
    plan never orphans half a swap."""
    def m(a):
        return jnp.where(keep, a, jnp.int32(-1))

    return MigrationPlan(
        m(plan.pro_layer), m(plan.pro_batch), m(plan.pro_src),
        m(plan.pro_dst), m(plan.pro_logical),
        m(plan.dem_layer), m(plan.dem_batch), m(plan.dem_src),
        m(plan.dem_dst), m(plan.dem_logical))


def revalidate_plan(plan: MigrationPlan, cache: PagedKVCache
                    ) -> MigrationPlan:
    """Hazard-mask a STAGED plan against the commit-time owner maps.

    In overlap mode (`EngineConfig.overlap_migrations`) a plan is built
    at step N and commits at step N+1, so the steps in between — the
    next decode's fresh-page allocation (`allocate_token_page`), the
    prefill plane's page registration, a competing commit — may have
    changed the placement the plan assumed. A promote row survives only
    when the world still matches the plan:

      * its source host slot still holds the planned logical page
        (``host_owner[src] == logical`` — a release, re-admission, or
        earlier promote of that page invalidates the row);
      * its destination is still what the plan paired it with: the
        planned victim for swap rows (``hbm_owner[dem_src] ==
        dem_logical``), a still-free slot for fill rows
        (``hbm_owner[dst] < 0`` — a decode/prefill allocation into the
        slot in the interim kills the row rather than letting the
        commit clobber a page the in-flight step just wrote).

    Demote rows are masked with the SAME row mask (index-paired swaps,
    as in `faults.throttle_plan`). This is values-only masking over the
    fixed-capacity plan — jit-safe, zero retraces — and it makes the
    staged commit idempotent against every in-flight mutation the scan
    can produce; the one hazard owner maps cannot express (a released
    lane re-bound to a DIFFERENT request with the same deterministic
    static placement) is handled by `mask_plan_lanes` at chunk
    boundaries.
    """
    ho, eo = cache.hbm_owner, cache.host_owner
    Ph, Pe = ho.shape[2], eo.shape[2]

    def gather(owner, l, b, s, bound):
        return owner[jnp.clip(l, 0, owner.shape[0] - 1),
                     jnp.maximum(b, 0),
                     jnp.clip(s, 0, bound - 1)]

    live = plan.pro_layer >= 0
    src_owner = gather(eo, plan.pro_layer, plan.pro_batch,
                       plan.pro_src, Pe)
    src_ok = src_owner == plan.pro_logical
    dst_owner = gather(ho, plan.pro_layer, plan.pro_batch,
                       plan.pro_dst, Ph)
    swap = plan.dem_layer >= 0
    victim_owner = gather(ho, plan.dem_layer, plan.dem_batch,
                          plan.dem_src, Ph)
    dst_ok = jnp.where(swap, victim_owner == plan.dem_logical,
                       dst_owner < 0)
    return _mask_plan_rows(plan, live & src_ok & dst_ok)


def mask_plan_lanes(plan: MigrationPlan, stale: jax.Array
                    ) -> MigrationPlan:
    """Drop every staged row targeting a `stale` lane (bool [B]).

    The chunk-boundary half of overlap-mode hazard masking: a plan
    staged in the previous chunk may reference a lane the host released
    or (re)admitted at the boundary. `revalidate_plan` cannot catch the
    reuse case — static placement is deterministic, so a re-admitted
    request can reproduce the exact (slot, logical) pairs of the
    evicted one with a DIFFERENT request's pages — so the engine masks
    freshly (re)bound lanes out of the staged buffer explicitly before
    the chunk runs (tests/test_serve_trace.py lane-reuse pin)."""
    lane = jnp.maximum(plan.pro_batch, 0)
    keep = (plan.pro_layer >= 0) & ~stale[lane]
    return _mask_plan_rows(plan, keep)


def slot_scores(values: jax.Array, owner: jax.Array) -> jax.Array:
    """Gather per-logical-page `values` [L, B, max_pages] to per-slot
    scores [L, B, P] through an owner map; free slots score -inf."""
    gathered = jnp.take_along_axis(values, jnp.maximum(owner, 0), axis=-1)
    return jnp.where(owner >= 0, gathered, jnp.float32(-jnp.inf))


def plan_migrations(cache: PagedKVCache, *, budget: int,
                    promote_thresh: float,
                    active: Optional[jax.Array] = None,
                    ) -> Tuple[MigrationPlan, jax.Array, jax.Array]:
    """Importance-EMA hysteresis planner, vectorized over [L, B].

    The `importance` device policy: `plan_by_score` over the
    attention-mass EMA — the hottest host-resident pages above
    `promote_thresh` displace the coldest HBM residents.
    """
    imp = cache.importance                                         # [L,B,P]
    host_imp = slot_scores(imp, cache.host_owner)
    hbm_imp = slot_scores(imp, cache.hbm_owner)
    return plan_by_score(cache, host_imp, hbm_imp, budget=budget,
                         promote_thresh=promote_thresh, active=active)


# --------------------------------------------------------------------------
# per-slot (batch-lane) ops for the continuous-batching serve loop.
# All are jit-safe [L, B]-vectorized: the fused step runs every lane and
# these gate which lanes' state survives, so admissions/completions never
# change traced shapes (zero retraces across the request stream).
# --------------------------------------------------------------------------

def lane_modes(active: jax.Array, prefilled: jax.Array,
               prompt_len: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-lane mode flags for a MIXED prefill+decode serve step.

    Returns (prefilling, decoding), disjoint bool [B]: a live lane
    prefills until its prompt is fully consumed, then decodes. The
    split is computed on device from the chunk carry, so a lane flips
    from prefill to decode mid-chunk without any host involvement —
    and it gates the whole control plane: the decode plane's write-slot
    choice / Quest masking / sampling apply to decoding lanes (the
    decode plane still RUNS every lane, but the others take the NO_WRITE
    write slot, so their token writes nothing to the pools or tables,
    and `lane_merge` restores their length and importance), while
    `plan_migrations(active=decoding)` keeps the migration planner off
    half-prefilled lanes so chunked prefill lands exactly the Static
    Placement that `prefill_cache` would (the
    bitwise-parity anchor). Half-filled prefill pages stay
    placement-visible throughout: `allocate_prompt_pages` registers
    them in the owner maps, so `occupancy` telemetry and the next
    step's write-slot choice count them as resident.
    """
    prefilling = active & (prefilled < prompt_len)
    return prefilling, active & ~prefilling

def _lane_bcast(active: jax.Array, ndim: int, axis: int) -> jax.Array:
    """Reshape a [B] lane mask to broadcast at `axis` of an ndim array."""
    shape = [1] * ndim
    shape[axis] = active.shape[0]
    return active.reshape(shape)


def lane_merge(old: PagedKVCache, new: PagedKVCache,
               active: jax.Array) -> PagedKVCache:
    """Keep `new` for active lanes, `old` for the rest (active bool [B]),
    of what a decode step changes in every lane: `length` and the
    `importance` EMA. Pools, page table and owner maps come from `new`
    whole: a lane outside `active` wrote nothing there, because its
    write slot was NO_WRITE (`choose_write_slot(cache, active)`).

    With `active` all-True this is a bitwise identity on `new`, which is
    what makes a single-request `serve` reproduce `generate` exactly.
    """
    return dataclasses.replace(
        new, length=jnp.where(active, new.length, old.length),
        importance=jnp.where(_lane_bcast(active, new.importance.ndim, 1),
                             new.importance, old.importance))


def release_lanes(cache: PagedKVCache, lanes: jax.Array) -> PagedKVCache:
    """Reclaim completed lanes (bool [B]): every page they own returns to
    the free pool — owner maps and page table cleared, length zeroed,
    importance reset — so `choose_write_slot` and `plan_migrations` see
    the slots as free destinations immediately. Pool data is left in
    place (unreachable once unmapped)."""
    def clr(arr, fill):
        return jnp.where(_lane_bcast(lanes, arr.ndim, 1), fill, arr)

    return dataclasses.replace(
        cache,
        page_table=clr(cache.page_table, NO_SLOT),
        hbm_owner=clr(cache.hbm_owner, NO_SLOT),
        host_owner=clr(cache.host_owner, NO_SLOT),
        length=jnp.where(lanes, 0, cache.length),
        importance=clr(cache.importance, 0.0))


def page_tiers(cache: PagedKVCache) -> jax.Array:
    """Read-time placement codes, int8 [L, B, max_pages]: 0 = HBM,
    1 = host DRAM, -1 = unallocated (`core.placement.base` tier codes).

    The batched telemetry channel of the trace bridge: sampled
    post-decode / pre-migration inside the fused step, this is the
    placement the step's attention reads actually hit — `generate`
    capture keeps lane 0, `serve` capture keeps every lane so the
    bridge can attribute per-request streams (see
    `repro.serving.trace_bridge`).
    """
    slot = cache.page_table                                 # [L, B, P]
    hbm_pages = cache.k_hbm.shape[2]
    return jnp.where(
        slot < 0, jnp.int8(-1),
        jnp.where(slot < hbm_pages, jnp.int8(0), jnp.int8(1)))


def occupancy(cache: PagedKVCache) -> jax.Array:
    """[2] int32: resident page counts (HBM, host) summed over [L, B] —
    the per-step read traffic in pages for Eq. (3)/(4) telemetry."""
    return jnp.stack([(cache.hbm_owner >= 0).sum(),
                      (cache.host_owner >= 0).sum()]).astype(jnp.int32)
