"""jit-safe page migration between the HBM and host tiers.

The control plane (`repro.serving.engine` / a placement policy) decides
WHAT moves; this module executes a batch of moves inside jit with
static shapes: both directions take fixed-size index arrays padded with
-1 rows. Padded rows are routed to out-of-bounds indices and dropped by
the scatter (`mode="drop"`) — NOT masked via gather+select, which would
both read stale values and collide on duplicate clamped indices.

Execution is an explicit TWO-PHASE commit (PR 8, the async-migration
split): `stage_plan` gathers every source page from the input pools
into a staging buffer, and `commit_staged` scatters the buffer into the
destination pools and rewrites the maps. `apply_migrations` — the
inline path every pre-overlap call site uses — is exactly
stage-then-commit with zero lag, so the split is bitwise-invisible to
it (pinned by tests/test_async_migration.py). The overlap serve
pipeline (`EngineConfig.overlap_migrations`) threads a staged
`MigrationPlan` through the scan carry instead and commits it one step
later, concurrently with the next step's decode compute; hazard masking
for that lag lives in `repro.serving.control.revalidate_plan`.

Both pools are ordinary device arrays, so on a TPU the cross-pool
scatter is an HBM-to-HBM copy: the DRAM tier is HBM-resident until the
host pool moves to host memory (ROADMAP queue 1 item 2), and only then
does a move become a transfer over the host link — the M_i / M_o
traffic of Eq. (3)/(4). The byte accounting used by the simulator and
by the engine's telemetry already counts those moves 1:1.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kvcache.paged import NO_SLOT, PagedKVCache


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MigrationPlan:
    """Fixed-capacity migration batch. All arrays [M]; -1 rows are no-ops.

    promote: host slot `src` -> hbm slot `dst` (page `logical`)
    demote:  hbm slot `src`  -> host slot `dst`
    Every entry also names the (layer, batch) coordinate.
    """
    pro_layer: jax.Array
    pro_batch: jax.Array
    pro_src: jax.Array      # host slot
    pro_dst: jax.Array      # hbm slot
    pro_logical: jax.Array
    dem_layer: jax.Array
    dem_batch: jax.Array
    dem_src: jax.Array      # hbm slot
    dem_dst: jax.Array      # host slot
    dem_logical: jax.Array

    @classmethod
    def empty(cls, capacity: int) -> "MigrationPlan":
        # ten DISTINCT buffers, not one aliased array: the overlap
        # serve loop donates the empty plan as the initial scan carry,
        # and XLA rejects donating the same buffer twice
        return cls(*[jnp.full((capacity,), -1, jnp.int32)
                     for _ in range(10)])

    @classmethod
    def build(cls, capacity: int, promotes, demotes) -> "MigrationPlan":
        """promotes/demotes: iterables of (layer, batch, src, dst, logical).

        `capacity` must be a per-geometry constant (see
        `repro.serving.control.plan_capacity`), NOT derived from the
        number of rows — a row-count capacity gives `apply_migrations`
        a different traced shape on nearly every step and recompiles it
        for each distinct promote/demote count.
        """
        import numpy as np

        def pack(rows):
            arr = np.full((capacity, 5), -1, np.int32)
            rows = list(rows)[:capacity]
            if rows:
                arr[: len(rows)] = np.asarray(rows, np.int32)
            return [jnp.asarray(arr[:, i]) for i in range(5)]
        return cls(*pack(promotes), *pack(demotes))

    @property
    def capacity(self) -> int:
        return self.pro_layer.shape[0]

    def row_counts(self) -> Tuple[jax.Array, jax.Array]:
        """(n_promotes, n_demotes) actually encoded in the plan — the
        non-sentinel rows. jit-safe; matches the counts a planner
        returned when it built the plan (telemetry cross-check)."""
        return (jnp.sum(self.pro_layer >= 0), jnp.sum(self.dem_layer >= 0))


def _oob(idx, ok, bound):
    """Route masked rows out of bounds (dropped by mode='drop').
    Sentinels must be OOB-HIGH: negative indices wrap NumPy-style."""
    return jnp.where(ok, idx, jnp.int32(bound))


def stage_plan(cache: PagedKVCache, plan: MigrationPlan
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Phase 1 of the two-phase commit: gather every source page.

    Returns `(dem_k, dem_v, pro_k, pro_v)`, each [M, T, KH, HD] — the
    HBM pages the plan demotes and the host pages it promotes, read
    from the INPUT pools before any scatter runs. Staging first is what
    makes a swap safe: a demotion whose destination is the host slot
    being vacated by a promotion (``dem_dst == pro_src``) reads the
    promoted page before the victim overwrites its slot — the
    gather-before-scatter discipline the engine has relied on since the
    first fused step. Sentinel (-1) rows gather an arbitrary in-bounds
    page; `commit_staged` routes them out of bounds and drops them.
    """
    L = cache.k_hbm.shape[0]
    hbm_pages = cache.k_hbm.shape[2]
    host_pages = cache.k_host.shape[2]
    d_l = jnp.clip(plan.dem_layer, 0, L - 1)
    d_b = jnp.maximum(plan.dem_batch, 0)
    d_src = jnp.clip(plan.dem_src, 0, hbm_pages - 1)
    dem_k = cache.k_hbm[d_l, d_b, d_src]          # [M, T, KH, HD]
    dem_v = cache.v_hbm[d_l, d_b, d_src]
    p_l = jnp.clip(plan.pro_layer, 0, L - 1)
    p_b = jnp.maximum(plan.pro_batch, 0)
    p_src = jnp.clip(plan.pro_src, 0, host_pages - 1)
    pro_k = cache.k_host[p_l, p_b, p_src]
    pro_v = cache.v_host[p_l, p_b, p_src]
    return dem_k, dem_v, pro_k, pro_v


def commit_staged(cache: PagedKVCache, plan: MigrationPlan,
                  staged: Tuple[jax.Array, jax.Array, jax.Array, jax.Array]
                  ) -> PagedKVCache:
    """Phase 2 of the two-phase commit: scatter the staged pages and
    rewrite the maps. Shapes are static in `plan`.

    `staged` is `stage_plan`'s gather of the SAME plan. Sentinel rows
    scatter to out-of-bounds indices (`mode="drop"`). Owner clears land
    before owner sets, so swapped slots end up owned by the arriving
    page, not marked free. The caller owns hazard ordering: when the
    commit lags the plan (overlap mode), it must first mask rows the
    interim steps invalidated (`control.revalidate_plan`) and re-stage
    against the commit-time pools.
    """
    dem_k, dem_v, pro_k, pro_v = staged
    k_hbm, v_hbm = cache.k_hbm, cache.v_hbm
    k_host, v_host = cache.k_host, cache.v_host
    page_table = cache.page_table
    hbm_owner, host_owner = cache.hbm_owner, cache.host_owner
    L = k_hbm.shape[0]
    hbm_pages = k_hbm.shape[2]
    host_pages = k_host.shape[2]
    max_pages = page_table.shape[2]

    # ---- index prep --------------------------------------------------------
    d_ok = plan.dem_layer >= 0
    d_l = _oob(plan.dem_layer, d_ok, L)
    d_b = jnp.maximum(plan.dem_batch, 0)
    d_src = jnp.minimum(jnp.maximum(plan.dem_src, 0), hbm_pages - 1)
    d_dst = _oob(plan.dem_dst, d_ok, host_pages)
    d_logical = _oob(plan.dem_logical, d_ok, max_pages)

    p_ok = plan.pro_layer >= 0
    p_l = _oob(plan.pro_layer, p_ok, L)
    p_b = jnp.maximum(plan.pro_batch, 0)
    p_src = jnp.minimum(jnp.maximum(plan.pro_src, 0), host_pages - 1)
    p_dst = _oob(plan.pro_dst, p_ok, hbm_pages)
    p_logical = _oob(plan.pro_logical, p_ok, max_pages)

    # ---- scatter data ------------------------------------------------------
    k_host = k_host.at[d_l, d_b, d_dst].set(dem_k, mode="drop")
    v_host = v_host.at[d_l, d_b, d_dst].set(dem_v, mode="drop")
    k_hbm = k_hbm.at[p_l, p_b, p_dst].set(pro_k, mode="drop")
    v_hbm = v_hbm.at[p_l, p_b, p_dst].set(pro_v, mode="drop")

    # ---- owner maps: clear vacated slots FIRST, then record arrivals -------
    hbm_owner = hbm_owner.at[d_l, d_b, _oob(plan.dem_src, d_ok, hbm_pages)] \
        .set(jnp.full_like(d_src, NO_SLOT), mode="drop")
    hbm_owner = hbm_owner.at[p_l, p_b, p_dst].set(
        jnp.where(p_ok, p_logical, NO_SLOT), mode="drop")
    host_owner = host_owner.at[p_l, p_b, _oob(plan.pro_src, p_ok, host_pages)] \
        .set(jnp.full_like(p_src, NO_SLOT), mode="drop")
    host_owner = host_owner.at[d_l, d_b, d_dst].set(
        jnp.where(d_ok, d_logical, NO_SLOT), mode="drop")

    # ---- page table --------------------------------------------------------
    page_table = page_table.at[d_l, d_b, d_logical].set(
        d_dst + hbm_pages, mode="drop")
    page_table = page_table.at[p_l, p_b, p_logical].set(p_dst, mode="drop")

    return dataclasses.replace(
        cache, k_hbm=k_hbm, v_hbm=v_hbm, k_host=k_host, v_host=v_host,
        page_table=page_table, hbm_owner=hbm_owner, host_owner=host_owner)


def apply_migrations(cache: PagedKVCache,
                     plan: MigrationPlan) -> PagedKVCache:
    """Execute a migration batch inline: two-phase commit with zero lag.

    Exactly `commit_staged(cache, plan, stage_plan(cache, plan))` — the
    pre-overlap call sites (the inline serve step, `step`/`run`/
    `generate`) keep this entry point, and the two-phase split is
    bitwise-invisible to them (tests/test_async_migration.py).
    """
    return commit_staged(cache, plan, stage_plan(cache, plan))


def migration_bytes(plan: MigrationPlan, page_bytes: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """(M_i, M_o) bytes for Eq. (3)/(4) telemetry."""
    m_i = jnp.sum(plan.pro_layer >= 0) * page_bytes
    m_o = jnp.sum(plan.dem_layer >= 0) * page_bytes
    return m_i, m_o
