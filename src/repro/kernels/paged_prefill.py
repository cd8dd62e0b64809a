"""Pallas TPU kernel: chunked-prefill attention over a lane's own pages.

A prefill step runs a `[B, C]` prompt slice per lane against the two
tiers of that lane's paged pool. Its oracle
(`layers.prefix_chunk_attention` over the concatenated pools) reads
every slot of every lane, every KV head repeated to its query heads.
This kernel reads only the pages a prefilling lane holds, up to its
slice's last position: a lane with no prompt tokens this step costs no
DMA and no arithmetic.

TPU mapping decisions:
  * Prefilling lanes are under static placement, so a lane's logical
    page p is HBM slot p for p < Ph and host slot p - Ph otherwise: the
    walk is a range, not a list. Lane b walks
    ceil((start[b] + n_valid[b]) / T) pages (0 where n_valid[b] == 0),
    a count handed in as scalar prefetch (SMEM).
  * Both tiers are walked in one loop over blocks of `pages_per_block`
    pages (the decode kernel's rule), double-buffered as in
    `paged_attention`: block j+1, or the next prefilling lane's first
    block, is in flight while block j is computed. A page picks its
    tier's pool when its DMA starts, so a block may straddle the tier
    boundary.
  * Queries are grouped by KV head, `[C*G, HD]` rows a head (row
    r = j*G + g is slice position j, query head g of the group), so no
    KV head is repeated. A page keeps `(KH, HD)` as its last two dims;
    the block is widened to float32 once into a `[K*T*KH, HD]` scratch,
    from which each head's `[K*T, HD]` keys are one sublane-strided
    load. Scores and P.V are bf16 MXU matmuls with float32 results, a
    block's K*T keys wide.
  * Key k of block i sits at position i*K*T + k. Row r sees it iff
    (key - start) * G <= r, the causal mask `key <= start + r // G`
    without a division, and only while r < n_valid * G: padding rows
    and tokens an earlier request left past the slice stay invisible.
  * The running softmax (m, l, acc) lives in float32 VMEM scratch per
    KV head. A row that sees no key (a padding row, a lane with no
    pages) ends with l == 0 and yields zeros. No per-page LSE: prefill
    updates no importance.

Grid: (B,) over lanes; each lane loops over ceil(count / pages_per_block)
blocks, a trip count read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import NEG_INF, pages_per_block


def _kernel(count_ref, first_ref, next_ref, start_ref, n_valid_ref,  # SMEM
            q_ref, kh_hbm, vh_hbm, ke_hbm, ve_hbm,     # q block; pools
            out_ref,
            k_buf, v_buf, k_wide, v_wide, m_ref, l_ref, acc_ref, sems,
            *, group: int, hbm_pages: int, host_pages: int):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    _, K, T, KH, HD = k_buf.shape
    R = q_ref.shape[1]
    KT = K * T
    scale = HD ** -0.5
    n_blocks = (count_ref[b] + K - 1) // K
    tiers = ((kh_hbm, vh_hbm, 0, hbm_pages),
             (ke_hbm, ve_hbm, hbm_pages, host_pages))
    last_page = hbm_pages + host_pages - 1

    def start_block(lane, blk, buf):
        """Start the 2K page DMAs of block `blk` of `lane` into `buf`;
        pages past the pool (a last block's tail) re-read its last
        slot, which the causal mask hides."""
        for p in range(K):
            page = jnp.minimum(blk * K + p, last_page)
            for k_pool, v_pool, lo, n in tiers:
                @pl.when((page >= lo) & (page < lo + n))
                def _start():
                    for pool, dst, sem in ((k_pool, k_buf, sems.at[0, buf]),
                                           (v_pool, v_buf, sems.at[1, buf])):
                        pltpu.make_async_copy(pool.at[lane, page - lo],
                                              dst.at[buf, p], sem).start()

    def wait_block(buf):
        k_pool, v_pool = tiers[0][:2]
        for p in range(K):
            for pool, dst, sem in ((k_pool, k_buf, sems.at[0, buf]),
                                   (v_pool, v_buf, sems.at[1, buf])):
                pltpu.make_async_copy(pool.at[0, 0], dst.at[buf, p],
                                      sem).wait()

    # the first prefilling lane fetches its own first block; every other
    # one was started by the previous prefilling lane's last block
    @pl.when((n_blocks > 0) & (first_ref[b] == 0))
    def _prime():
        start_block(b, 0, 0)

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, KT), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, KT), 1)
    start = start_ref[b]
    row_live = row < n_valid_ref[b] * group

    def block(i, carry):
        buf = (first_ref[b] + i) % 2
        nxt = next_ref[b]

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(b, i + 1, 1 - buf)

        @pl.when((i + 1 == n_blocks) & (nxt < n_lanes))
        def _next_lane():
            start_block(nxt, 0, 1 - buf)

        wait_block(buf)
        k_wide[...] = k_buf[buf].astype(jnp.float32).reshape(KT * KH, HD)
        v_wide[...] = v_buf[buf].astype(jnp.float32).reshape(KT * KH, HD)
        seen = row_live & ((i * KT + col - start) * group <= row)
        for h in range(KH):
            q = q_ref[h]                                       # [R, HD]
            k = k_wide[pl.ds(h, KT, stride=KH), :].astype(q.dtype)
            v = v_wide[pl.ds(h, KT, stride=KH), :].astype(q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * scale, NEG_INF)
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_old - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q: jax.Array, k_hbm: jax.Array,
                            v_hbm: jax.Array, k_host: jax.Array,
                            v_host: jax.Array, start: jax.Array,
                            n_valid: jax.Array, *,
                            interpret: bool = True) -> jax.Array:
    """Causal attention of each lane's prompt slice against its pages.

    q: [B, C, H, HD], slice position j of lane b at absolute position
    start[b] + j, real while j < n_valid[b]; k_hbm/v_hbm
    [B, Ph, T, KH, HD] and k_host/v_host [B, Pe, T, KH, HD] hold the
    lane's tokens at static placement (logical page p in HBM slot p,
    then host slot p - Ph), the slice's own K/V already written.
    Returns [B, C, H, HD]: for real rows, `prefix_chunk_attention` over
    the concatenated pools; zeros for padding rows and for lanes with
    n_valid == 0.
    """
    B, C, H, HD = q.shape
    Ph, T, KH = k_hbm.shape[1:4]
    Pe = k_host.shape[1]
    G = H // KH
    R = C * G
    K = pages_per_block(T, KH, HD, k_hbm.dtype.itemsize, Ph + Pe)

    start = start.astype(jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    count = jnp.where(n_valid > 0,
                      jnp.minimum(-(-(start + n_valid) // T), Ph + Pe), 0)
    blocks = (count + K - 1) // K
    first = jnp.cumsum(blocks) - blocks         # blocks before each lane
    lanes = jnp.arange(B, dtype=jnp.int32)
    later = (lanes[None, :] > lanes[:, None]) & (count[None, :] > 0)
    nxt = jnp.min(jnp.where(later, lanes[None, :], B), axis=1)

    # rows grouped by KV head: [B, KH, C*G, HD], row j*G + g
    qg = q.reshape(B, C, KH, G, HD).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KH, R, HD)

    def lane_map(b, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, KH, R, HD), lane_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=pl.BlockSpec((None, KH, R, HD), lane_map),
        scratch_shapes=[
            pltpu.VMEM((2, K, T, KH, HD), k_hbm.dtype),
            pltpu.VMEM((2, K, T, KH, HD), v_hbm.dtype),
            pltpu.VMEM((K * T * KH, HD), jnp.float32),
            pltpu.VMEM((K * T * KH, HD), jnp.float32),
            pltpu.VMEM((KH, R, 1), jnp.float32),
            pltpu.VMEM((KH, R, 1), jnp.float32),
            pltpu.VMEM((KH, R, HD), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_kernel, group=G, hbm_pages=Ph,
                               host_pages=Pe)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, R, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_prefill_attention",
    )(count, first, nxt, start, n_valid, qg, k_hbm, v_hbm, k_host, v_host)
    out = out.reshape(B, KH, C, G, HD).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H, HD)
