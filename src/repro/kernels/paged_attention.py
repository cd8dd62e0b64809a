"""Pallas TPU kernel: paged GQA decode attention over ONE memory tier.

This is the compute hot-spot of the paper's serving path: every decode
step streams resident KV pages and produces (a) the partial attention
output for that tier and (b) per-page log-sum-exp scores that the
placement policy uses as token-importance statistics — so importance
tracking is free, fused into the attention read pass.

TPU mapping decisions (HARDWARE ADAPTATION notes):
  * The kernel walks only the pages a lane holds. The wrapper compacts
    the tier's page list (live entries first, in list order) and hands
    the kernel the compact list, its valid counts and a per-lane count
    as scalar-prefetch operands (SMEM). A lane with no page costs no
    DMA and no arithmetic; empty slots and Quest-masked pages are never
    visited.
  * The pools stay in HBM (`memory_space=pl.ANY`). A lane's live pages
    are copied in blocks of `pages_per_block` whole `(T, KH, HD)` pages
    by async DMAs into a double-buffered VMEM scratch: block j+1 (or
    the next lane's first block) is in flight while block j is
    computed. Whole pages keep `(KH, HD)` as the last two dims, so
    Mosaic's (8, 128) tiling holds for every head count and the pools
    are never relaid out.
  * `pages_per_block` comes from the page's bytes and the tier's slot
    count: up to `BLOCK_PAGES` pages, fewer where a block would pass
    `BLOCK_BYTES` of K, the tier's slots or the VMEM budget. A block's
    pages are unrolled, so the block amortises the loop and DMA-issue
    overhead; past 8 pages the per-page arithmetic sets the pace at
    every head count the cells run.
  * Each page keeps its own arithmetic, in list order: all query heads
    of a lane form ONE `[KH*G, HD]` operand and the page ONE
    `[T*KH, HD]` operand, so scores are a single 2-D matmul; the
    cross-head products are masked out (column c belongs to kv head
    c % KH, row r to kv head r // G). That costs KH-fold MXU work, and
    with the softmax it is what a page costs: a live page's 2*T*KH*HD
    bytes take less time to read than to compute on. A page
    the compaction skips was an exact no-op of the running softmax, so
    the walk gives the same out, m and l as visiting every list entry.
  * Running softmax state (m, l, acc) is carried in registers through
    the block loop. The last block's tail past the lane's count holds
    no valid token: its DMAs and arithmetic are spent, at
    most `pages_per_block` - 1 pages a lane, and change no bit of the
    result. Per-page LSEs are written one `[KH*G, pages_per_block]`
    row block per page block, in compact order; the wrapper puts them
    back into list order.

Grid: (B,) over lanes; each lane loops over ceil(count / pages_per_block)
blocks, a trip count read from SMEM.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: most pages a block holds: the block's pages are unrolled, and past 8
#: their arithmetic, not the block's DMA, sets the pace
BLOCK_PAGES = 8
#: most bytes of K one block moves (V moves as many)
BLOCK_BYTES = 256 * 1024
#: VMEM the double-buffered K and V blocks may take together
BLOCK_VMEM_BYTES = 8 * 1024 * 1024


def _vmem_page_bytes(T: int, KH: int, HD: int, itemsize: int) -> int:
    """A page's VMEM footprint: KH rounds up to the dtype's sublane
    tile (8 rows of 32 bits)."""
    rows = 8 * (4 // itemsize)
    return T * (-(-KH // rows) * rows) * HD * itemsize


def pages_per_block(T: int, KH: int, HD: int, itemsize: int,
                    n_slots: int) -> int:
    """Pages one DMA block holds: the largest power of two of at most
    `BLOCK_PAGES` pages whose K bytes stay within `BLOCK_BYTES`, no more
    than the tier's slots, and whose double-buffered K and V blocks fit
    `BLOCK_VMEM_BYTES`."""
    page = T * KH * HD * itemsize
    fit = BLOCK_VMEM_BYTES // (4 * _vmem_page_bytes(T, KH, HD, itemsize))
    k = max(1, min(BLOCK_PAGES, BLOCK_BYTES // page, fit, n_slots))
    return 1 << (k.bit_length() - 1)


def _kernel(count_ref, first_ref, next_ref, slot_ref, valid_ref,  # SMEM
            q_ref, k_hbm, v_hbm,                  # q block; pools in HBM
            out_ref, m_out_ref, l_out_ref, lse_ref,   # outputs
            k_buf, v_buf, sems,                   # scratch
            *, kv_heads: int, group: int, n_list: int):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    _, K, T, KH, HD = k_buf.shape
    R = kv_heads * group
    scale = HD ** -0.5
    count = count_ref[b]
    n_blocks = (count + K - 1) // K

    def copies(lane, blk, buf):
        """The 2K page DMAs of block `blk` of `lane` into buffer `buf`."""
        out = []
        for p in range(K):
            slot = slot_ref[lane * n_list + blk * K + p]
            for pool, dst, sem in ((k_hbm, k_buf, sems.at[0, buf]),
                                   (v_hbm, v_buf, sems.at[1, buf])):
                out.append(pltpu.make_async_copy(
                    pool.at[lane, slot], dst.at[buf, p], sem))
        return out

    # the first non-empty lane fetches its own first block; every other
    # one was started by the previous non-empty lane's last block
    @pl.when((n_blocks > 0) & (first_ref[b] == 0))
    def _prime():
        for c in copies(b, 0, 0):
            c.start()

    q = q_ref[...].astype(jnp.float32)                       # [R, HD]
    row = jax.lax.broadcasted_iota(jnp.int32, (R, T * KH), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, T * KH), 1)
    own = (col % kv_heads) == (row // group)
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (R, K), 1)

    def block(i, state):
        buf = (first_ref[b] + i) % 2
        nxt = next_ref[b]

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            for c in copies(b, i + 1, 1 - buf):
                c.start()

        @pl.when((i + 1 == n_blocks) & (nxt < n_lanes))
        def _next_lane():
            for c in copies(nxt, 0, 1 - buf):
                c.start()

        for c in copies(b, i, buf):
            c.wait()

        def page(p, carry):
            m_old, l_old, acc_old, lse_blk = carry
            k = k_buf[buf, p].astype(jnp.float32).reshape(T * KH, HD)
            v = v_buf[buf, p].astype(jnp.float32).reshape(T * KH, HD)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            # validity: own kv head, token offset < the page's valid count
            n_valid = valid_ref[b * n_list + i * K + p]
            valid = own & ((col // kv_heads) < n_valid)
            s = jnp.where(valid, s, NEG_INF)

            # per-page lse (independent of running state)
            m_p = jnp.max(s, axis=-1, keepdims=True)             # [R, 1]
            m_p_safe = jnp.where(m_p <= NEG_INF / 2, 0.0, m_p)
            p_loc = jnp.where(valid, jnp.exp(s - m_p_safe), 0.0)
            l_p = jnp.sum(p_loc, axis=-1, keepdims=True)          # [R, 1]
            lse_p = jnp.where(l_p > 0,
                              m_p_safe + jnp.log(jnp.maximum(l_p, 1e-37)),
                              NEG_INF)
            lse_blk = jnp.where(lane_of == p, lse_p, lse_blk)

            # running softmax update
            m_new = jnp.maximum(m_old, m_p)
            m_new_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            corr_old = jnp.where(m_old <= NEG_INF / 2, 0.0,
                                 jnp.exp(m_old - m_new_safe))
            corr_p = jnp.where(l_p > 0, jnp.exp(m_p_safe - m_new_safe), 0.0)
            pv = jax.lax.dot_general(p_loc, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            return (m_new, l_old * corr_old + l_p * corr_p,
                    acc_old * corr_old + pv * corr_p, lse_blk)

        # unrolled; the last block's tail past `count` holds no valid
        # token, an exact no-op of the running softmax
        carry = state + (jnp.full((R, K), NEG_INF, jnp.float32),)
        for p in range(K):
            carry = page(p, carry)
        m, l, acc, lse_blk = carry
        lse_ref[i] = lse_blk
        return m, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((R, 1), NEG_INF, jnp.float32),
         jnp.zeros((R, 1), jnp.float32), jnp.zeros((R, HD), jnp.float32)))
    out_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)
    m_out_ref[...] = m
    l_out_ref[...] = l


def compact_pages(page_list: jax.Array, page_valid: jax.Array):
    """A [B, N] page list's live entries (slot >= 0, valid > 0) first,
    in list order -> (slots, valid, count [B], rank).

    slots/valid are [B, N]; the tail past `count` holds the other
    entries' slots (holes clamped to slot 0) with 0 valid tokens.
    rank[b, n] is list entry n's position in the compact order, -1
    where the entry is not live."""
    B, N = page_list.shape
    live = (page_list >= 0) & (page_valid > 0)
    idx = jnp.arange(N, dtype=jnp.int32)
    # live entries sort by their index, the rest after them
    order = jnp.sort(jnp.where(live, idx, idx + N), axis=1) % N
    slots = jnp.maximum(jnp.take_along_axis(page_list, order, axis=1), 0)
    valid = jnp.where(jnp.take_along_axis(live, order, axis=1),
                      jnp.take_along_axis(page_valid, order, axis=1), 0)
    live32 = live.astype(jnp.int32)
    rank = jnp.where(live, jnp.cumsum(live32, axis=1) - 1, -1)
    return slots, valid.astype(jnp.int32), live32.sum(1), rank


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_list: jax.Array, page_valid: jax.Array,
                    *, interpret: bool = True,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Semantics identical to `repro.kernels.ref.paged_attention_ref`.

    q: [B, KH, G, HD]; k_pool/v_pool: [B, P, T, KH, HD];
    page_list/page_valid: [B, N] int32, holes (-1) anywhere. Returns
    (out [B, KH, G, HD], m, l [B, KH, G], page_lse [B, KH, G, N]) with
    page_lse in list order (NEG_INF where the entry holds no token).
    """
    B, KH, G, HD = q.shape
    T = k_pool.shape[2]
    N = page_list.shape[1]
    R = KH * G
    K = pages_per_block(T, KH, HD, k_pool.dtype.itemsize, N)
    n_blocks = -(-N // K)

    slots, valid, count, rank = compact_pages(page_list, page_valid)
    # pad the compact lists to whole blocks with slot 0, no valid token
    pad = n_blocks * K - N
    slots = jnp.pad(slots, ((0, 0), (0, pad))).reshape(-1)
    valid = jnp.pad(valid, ((0, 0), (0, pad))).reshape(-1)
    blocks = (count + K - 1) // K
    first = jnp.cumsum(blocks) - blocks         # blocks before each lane
    lanes = jnp.arange(B, dtype=jnp.int32)
    later = (lanes[None, :] > lanes[:, None]) & (count[None, :] > 0)
    nxt = jnp.min(jnp.where(later, lanes[None, :], B), axis=1)

    def lane_map(b, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, R, HD), lane_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, R, HD), lane_map),
            pl.BlockSpec((None, R, 1), lane_map),
            pl.BlockSpec((None, R, 1), lane_map),
            pl.BlockSpec((None, n_blocks, R, K), lambda b, *_: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, K, T, KH, HD), k_pool.dtype),
            pltpu.VMEM((2, K, T, KH, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )

    out_shapes = [
        jax.ShapeDtypeStruct((B, R, HD), q.dtype),
        jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, n_blocks, R, K), jnp.float32),
    ]

    kernel = functools.partial(_kernel, kv_heads=KH, group=G,
                               n_list=n_blocks * K)
    out, m, l, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(count, first, nxt, slots, valid, q.reshape(B, R, HD), k_pool, v_pool)
    # compact order -> list order; entries the walk skipped hold no token
    lse = lse.transpose(0, 2, 1, 3).reshape(B, R, n_blocks * K)
    lse = jnp.take_along_axis(lse, jnp.maximum(rank, 0)[:, None, :], axis=2)
    lse = jnp.where(rank[:, None, :] >= 0, lse, NEG_INF)
    return (out.reshape(B, KH, G, HD), m.reshape(B, KH, G),
            l.reshape(B, KH, G), lse.reshape(B, KH, G, N))
