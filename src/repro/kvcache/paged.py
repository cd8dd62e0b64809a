"""Two-tier paged KV cache — the paper's technique as a serving feature.

Physical layout (per attention layer, per batch element):

  k_hbm/v_hbm   [L, B, hbm_pages,  page_tokens, KH, HD]   "HBM tier"
  k_host/v_host [L, B, host_pages, page_tokens, KH, HD]   "DRAM tier"

Logical pages are mapped to physical slots by a single page table:

  page_table    [L, B, max_pages] int32 — physical slot of logical page p;
                slot < hbm_pages  -> HBM slot,
                slot >= hbm_pages -> host slot (slot - hbm_pages),
                NO_SLOT (=-1)     -> page not allocated yet.

Both pools are ordinary device arrays on every backend. On a TPU the
"DRAM tier" is therefore HBM-resident: the data path — page tables,
tier-split attention, migration traffic accounting — is the paper's,
but a tier crossing is an HBM-to-HBM copy, not a host-link DMA, until
the host pool moves to host memory and the kernel reads it page by
page (ROADMAP queue 1 item 2). Attention never reads a host-memory
operand directly: XLA refuses a `dot_general` whose inputs live in
different memory spaces.

The control plane (which page lives where) is host-side python in
`repro.serving.engine`; everything in this module is jit-safe.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

NO_SLOT = jnp.int32(-1)

#: EMA decay of the per-page attention-mass importance statistic
#: (`PagedKVCache.importance`), applied by the decode data plane every
#: step. Shared here so the device policies (repro.serving.policies)
#: can derive payback horizons from the same constant the kernel uses.
IMPORTANCE_EMA = 0.25


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    num_layers: int          # attention layers only
    batch: int
    page_tokens: int
    hbm_pages: int           # per layer per sequence
    host_pages: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def max_pages(self) -> int:
        return self.hbm_pages + self.host_pages

    @property
    def max_tokens(self) -> int:
        return self.max_pages * self.page_tokens

    def page_bytes(self) -> int:
        return (2 * self.page_tokens * self.kv_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    @classmethod
    def for_context(cls, *, num_layers: int, batch: int, context: int,
                    kv_heads: int, head_dim: int, page_tokens: int = 16,
                    hbm_fraction: float = 0.25, pad_to: int = 16,
                    dtype=jnp.bfloat16) -> "CacheGeometry":
        """Pool sizes are padded to `pad_to` so the PAGES dim divides the
        model mesh axis (pools are page-sharded when kv_heads doesn't
        divide it — sequence-parallel KV, see launch/shardings.py)."""
        def rnd(x):
            return -(-max(x, 1) // pad_to) * pad_to
        pages = -(-context // page_tokens)
        hbm = rnd(int(round(pages * hbm_fraction)))
        host = rnd(pages - hbm + 1)
        return cls(num_layers=num_layers, batch=batch,
                   page_tokens=page_tokens, hbm_pages=hbm,
                   host_pages=host, kv_heads=kv_heads,
                   head_dim=head_dim, dtype=dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    k_hbm: jax.Array       # [L, B, Ph, T, KH, HD]
    v_hbm: jax.Array
    k_host: jax.Array      # [L, B, Pe, T, KH, HD]
    v_host: jax.Array
    page_table: jax.Array  # [L, B, max_pages] int32 physical slot
    hbm_owner: jax.Array   # [L, B, Ph] int32 logical page at slot (-1 free)
    host_owner: jax.Array  # [L, B, Pe] int32
    length: jax.Array      # [B] int32 tokens currently cached
    importance: jax.Array  # [L, B, max_pages] f32 EMA of attention mass

    @property
    def geometry_like(self) -> Tuple[int, ...]:
        return self.k_hbm.shape

    def tier_lists(self, layer=None, logical_page_mask=None):
        """Kernel operands: per-tier (page_list, page_valid).

        page_list[b, s] = s if slot s is occupied else -1, so occupied
        slots keep their pool order; the kernel's wrapper compacts the
        list and the kernel walks only the occupied entries. page_valid
        is the number of cached tokens that fall inside the owning page.
        Returns arrays for one layer ([B, P]) or all ([L, B, P]).

        logical_page_mask (bool [L, B, max_pages] or [B, max_pages]):
        Quest-style dynamic token bypassing — pages whose mask is False
        are excluded from attention this step (their data stays cached;
        only the read is skipped).
        """
        def lists(owner, mask):
            T = self.k_hbm.shape[3]
            idx = jnp.arange(owner.shape[-1], dtype=jnp.int32)
            occupied = owner >= 0
            if mask is not None:
                sel = jnp.take_along_axis(
                    mask, jnp.maximum(owner, 0), axis=-1)
                occupied = occupied & sel
            plist = jnp.where(occupied, idx, NO_SLOT)
            tokens_before = owner * T
            valid = jnp.clip(self.length[..., :, None] - tokens_before, 0, T)
            valid = jnp.where(occupied, valid, 0).astype(jnp.int32)
            return plist, valid

        ho = self.hbm_owner if layer is None else self.hbm_owner[layer]
        eo = self.host_owner if layer is None else self.host_owner[layer]
        hl, hv = lists(ho, logical_page_mask)
        el, ev = lists(eo, logical_page_mask)
        return hl, hv, el, ev


def init_cache(geo: CacheGeometry) -> PagedKVCache:
    """A fresh all-free cache for `geo`."""
    L, B, T = geo.num_layers, geo.batch, geo.page_tokens
    kh, hd = geo.kv_heads, geo.head_dim
    shape_h = (L, B, geo.hbm_pages, T, kh, hd)
    shape_e = (L, B, geo.host_pages, T, kh, hd)
    return PagedKVCache(
        k_hbm=jnp.zeros(shape_h, geo.dtype),
        v_hbm=jnp.zeros(shape_h, geo.dtype),
        k_host=jnp.zeros(shape_e, geo.dtype),
        v_host=jnp.zeros(shape_e, geo.dtype),
        page_table=jnp.full((L, B, geo.max_pages), NO_SLOT, jnp.int32),
        hbm_owner=jnp.full((L, B, geo.hbm_pages), NO_SLOT, jnp.int32),
        host_owner=jnp.full((L, B, geo.host_pages), NO_SLOT, jnp.int32),
        length=jnp.zeros((B,), jnp.int32),
        importance=jnp.zeros((L, B, geo.max_pages), jnp.float32),
    )


def abstract_cache(geo: CacheGeometry) -> PagedKVCache:
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        jax.eval_shape(lambda: init_cache(geo)))


def page_of_token(token_idx, page_tokens: int):
    return token_idx // page_tokens, token_idx % page_tokens


def prefill_cache(geo: CacheGeometry, k: jax.Array, v: jax.Array,
                  length) -> PagedKVCache:
    """Populate a cache from prefill K/V (static placement: HBM first).

    k, v: [L, B, S, KH, HD] with RoPE already applied to k.
    length: int or [B] — prompt tokens actually valid (<= S).
    Logical page p maps to HBM slot p while p < hbm_pages, then host
    slot p - hbm_pages — exactly the paper's Static Placement; dynamic
    policies migrate afterwards.
    """
    L, B, S = k.shape[0], k.shape[1], k.shape[2]
    T = geo.page_tokens
    n_pages = -(-S // T)
    assert n_pages <= geo.max_pages, (n_pages, geo.max_pages)
    pad = n_pages * T - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kp = k.reshape(L, B, n_pages, T, geo.kv_heads, geo.head_dim)
    vp = v.reshape(L, B, n_pages, T, geo.kv_heads, geo.head_dim)

    cache = init_cache(geo)
    n_h = min(n_pages, geo.hbm_pages)
    k_hbm = cache.k_hbm.at[:, :, :n_h].set(kp[:, :, :n_h].astype(geo.dtype))
    v_hbm = cache.v_hbm.at[:, :, :n_h].set(vp[:, :, :n_h].astype(geo.dtype))
    n_e = n_pages - n_h
    if n_e > 0:
        k_host = cache.k_host.at[:, :, :n_e].set(
            kp[:, :, n_h:].astype(geo.dtype))
        v_host = cache.v_host.at[:, :, :n_e].set(
            vp[:, :, n_h:].astype(geo.dtype))
    else:
        k_host, v_host = cache.k_host, cache.v_host

    pages = jnp.arange(geo.max_pages, dtype=jnp.int32)
    table = jnp.where(pages < n_pages, pages, NO_SLOT)
    page_table = jnp.broadcast_to(table, (geo.num_layers, B, geo.max_pages))

    hslots = jnp.arange(geo.hbm_pages, dtype=jnp.int32)
    hbm_owner = jnp.where(hslots < n_h, hslots, NO_SLOT)
    hbm_owner = jnp.broadcast_to(hbm_owner, (geo.num_layers, B,
                                             geo.hbm_pages))
    eslots = jnp.arange(geo.host_pages, dtype=jnp.int32)
    host_owner = jnp.where(eslots < n_e, eslots + n_h, NO_SLOT)
    host_owner = jnp.broadcast_to(host_owner, (geo.num_layers, B,
                                               geo.host_pages))

    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    return PagedKVCache(
        k_hbm=k_hbm, v_hbm=v_hbm, k_host=k_host, v_host=v_host,
        page_table=page_table, hbm_owner=hbm_owner, host_owner=host_owner,
        length=length, importance=cache.importance)


def no_write_slot(cache: PagedKVCache) -> int:
    """NO_WRITE, the write slot of a lane that writes nothing this step.

    One past the last host slot (hbm_pages + host_pages): out of range
    in both tiers, so `write_token_layer` drops the lane's K/V and
    `allocate_token_page` its page-table and owner-map entries. The
    serve step gives it to every lane that is not decoding, which keeps
    such a lane's pools and tables bitwise as they were without a
    select over the pools."""
    return cache.k_hbm.shape[2] + cache.k_host.shape[2]


# ---------------------------------------------------------------------------
# jit-safe cache mutation primitives (operate on ONE layer slice)
# ---------------------------------------------------------------------------

def write_token_layer(k_hbm_l, v_hbm_l, k_host_l, v_host_l, slot, offset,
                      k_new, v_new):
    """Write one token's (k, v) into physical page `slot` at `offset`.

    Shapes: pools [B, P, T, KH, HD]; slot/offset [B] int32;
    k_new/v_new [B, KH, HD]. slot >= hbm_pages addresses the host pool;
    a lane whose slot is `no_write_slot` (NO_WRITE) writes nothing.
    """
    hbm_pages = k_hbm_l.shape[1]
    host_pages = k_host_l.shape[1]
    in_hbm = slot < hbm_pages
    # the tier a slot misses gets an out-of-range index and mode="drop":
    # one [B,KH,HD] scatter per pool, no gather+select round-trip of the
    # full pool (that pattern lowers to full-pool traffic). NO_WRITE is
    # out of range in both tiers, so a lane that is not decoding leaves
    # both pools bitwise as they were. NOTE: a sentinel must be
    # OOB-high — negative indices wrap NumPy-style before the scatter
    # and would hit the last page.
    host_slot = jnp.where(~in_hbm, slot - hbm_pages,
                          jnp.int32(host_pages))
    hbm_slot = jnp.where(in_hbm, slot, jnp.int32(hbm_pages))

    def upd(pool, s, val):
        b = pool.shape[0]
        bidx = jnp.arange(b)
        return pool.at[bidx, s, offset].set(val.astype(pool.dtype),
                                            mode="drop")

    k_hbm_l = upd(k_hbm_l, hbm_slot, k_new)
    v_hbm_l = upd(v_hbm_l, hbm_slot, v_new)
    k_host_l = upd(k_host_l, host_slot, k_new)
    v_host_l = upd(v_host_l, host_slot, v_new)
    return k_hbm_l, v_hbm_l, k_host_l, v_host_l


def write_tokens_layer(k_hbm_l, v_hbm_l, k_host_l, v_host_l, slot, offset,
                       k_new, v_new, valid):
    """Write a slice of tokens' (k, v) into physical pages (one layer).

    The chunked-prefill generalization of `write_token_layer`: pools
    [B, P, T, KH, HD]; slot/offset/valid [B, C] int32/bool; k_new/v_new
    [B, C, KH, HD]. slot >= hbm_pages addresses the host pool; entries
    with valid == False scatter to an OOB-high sentinel and are dropped
    (partial-page appends: a slice may start and end mid-page, and may
    straddle page and tier boundaries).
    """
    hbm_pages = k_hbm_l.shape[1]
    host_pages = k_host_l.shape[1]
    in_hbm = valid & (slot < hbm_pages)
    in_host = valid & (slot >= hbm_pages)
    hbm_slot = jnp.where(in_hbm, slot, jnp.int32(hbm_pages))
    host_slot = jnp.where(in_host, slot - hbm_pages, jnp.int32(host_pages))

    def upd(pool, s, val):
        bidx = jnp.arange(pool.shape[0])[:, None]
        return pool.at[bidx, s, offset].set(val.astype(pool.dtype),
                                            mode="drop")

    k_hbm_l = upd(k_hbm_l, hbm_slot, k_new)
    v_hbm_l = upd(v_hbm_l, hbm_slot, v_new)
    k_host_l = upd(k_host_l, host_slot, k_new)
    v_host_l = upd(v_host_l, host_slot, v_new)
    return k_hbm_l, v_hbm_l, k_host_l, v_host_l


def allocate_prompt_pages(cache: PagedKVCache, pos: jax.Array,
                          valid: jax.Array, n_new: jax.Array
                          ) -> PagedKVCache:
    """Register the logical pages receiving a prompt slice and bump
    lane lengths (chunked prefill at an offset).

    pos/valid: [B, C] absolute token positions and their validity;
    n_new: [B] tokens actually consumed per lane (0 for lanes not
    prefilling). Placement is the paper's Static Placement — logical
    page p maps to HBM slot p while p < hbm_pages, else host slot
    p - hbm_pages — exactly what `prefill_cache` produces, so a prompt
    prefilled chunk-by-chunk lands in the same physical slots as a
    whole-prompt prefill (the migration planner takes over only once
    the lane starts decoding). Half-filled pages are registered in the
    owner maps immediately, so occupancy telemetry and write-slot
    choice see them as resident ("placement-visible")."""
    T = cache.k_hbm.shape[3]
    hbm_pages = cache.k_hbm.shape[2]
    host_pages = cache.k_host.shape[2]
    L = cache.page_table.shape[0]
    max_pages = cache.page_table.shape[2]
    B, C = pos.shape
    page = (pos // T).astype(jnp.int32)
    lidx = jnp.arange(L)[:, None, None]
    bidx = jnp.arange(B)[None, :, None]

    pidx = jnp.where(valid, page, max_pages)[None]
    page_table = cache.page_table.at[lidx, bidx, pidx].set(
        page[None], mode="drop")
    hslot = jnp.where(valid & (page < hbm_pages), page, hbm_pages)[None]
    hbm_owner = cache.hbm_owner.at[lidx, bidx, hslot].set(
        page[None], mode="drop")
    eslot = jnp.where(valid & (page >= hbm_pages), page - hbm_pages,
                      host_pages)[None]
    host_owner = cache.host_owner.at[lidx, bidx, eslot].set(
        page[None], mode="drop")
    return dataclasses.replace(
        cache, page_table=page_table, hbm_owner=hbm_owner,
        host_owner=host_owner,
        length=cache.length + n_new.astype(cache.length.dtype))


def append_token(cache: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                 write_slot: jax.Array, write_offset: jax.Array
                 ) -> PagedKVCache:
    """Append one token's KV across all layers.

    k_new/v_new: [L, B, KH, HD]; write_slot: [L, B] physical page slot
    chosen by the control plane; write_offset: [B] offset within page.
    """
    def per_layer(args):
        kh, vh, ke, ve, kn, vn, slot = args
        return write_token_layer(kh, vh, ke, ve, slot, write_offset, kn, vn)

    kh, vh, ke, ve = jax.lax.map(
        per_layer, (cache.k_hbm, cache.v_hbm, cache.k_host, cache.v_host,
                    k_new, v_new, write_slot))
    return dataclasses.replace(cache, k_hbm=kh, v_hbm=vh, k_host=ke,
                               v_host=ve, length=cache.length + 1)
