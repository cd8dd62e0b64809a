"""Pallas TPU kernel: paged GQA decode attention over ONE memory tier.

This is the compute hot-spot of the paper's serving path: every decode
step streams resident KV pages and produces (a) the partial attention
output for that tier and (b) per-page log-sum-exp scores that the
placement policy uses as token-importance statistics — so importance
tracking is free, fused into the attention read pass.

TPU mapping decisions (HARDWARE ADAPTATION notes):
  * One grid step reads one whole page of one lane: the K/V block is
    `(T, KH, HD)` over the `[B, P, T, KH, HD]` pool, so its last two
    dims are the whole `(KH, HD)` and Mosaic's (8, 128) tiling rule
    holds for every head count. A `(T, 1, HD)` per-head block squeezes
    KH to 1 in the second-to-last position, which the TPU lowering
    refuses.
  * The page table is a scalar-prefetch operand
    (`pltpu.PrefetchScalarGridSpec`): the index_map dereferences
    page_list BEFORE the grid step runs, so Mosaic can overlap the
    page DMA of step i+1 with the FLOPs of step i — the TPU analogue
    of the paper's overlap of link transfers and HBM reads.
  * All query heads of a lane form ONE `[KH*G, HD]` operand and the
    page ONE `[T*KH, HD]` operand, so scores are a single 2-D matmul;
    the cross-head products are masked out (column c belongs to kv
    head c % KH, row r to kv head r // G). Decode is bandwidth-bound,
    so the KH-fold extra MXU work costs nothing a page read does not.
  * Running softmax state (m, l, acc) lives in VMEM scratch. Per-page
    LSEs accumulate in a resident `[KH*G, 128]` output block (128 pages
    per block, one lane column per page) and are written back once per
    128 pages.

Grid: (B, N) with N = pages in the tier's page list (innermost,
sequential).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: pages per resident block of the per-page LSE output (one lane each)
LSE_LANES = 128


def _kernel(page_list_ref, page_valid_ref,   # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,             # VMEM blocks
            out_ref, m_out_ref, l_out_ref, lse_ref,   # outputs
            m_scr, l_scr, acc_scr,           # scratch
            *, kv_heads: int, group: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    T, KH, HD = k_ref.shape
    q = q_ref[...].astype(jnp.float32)                      # [R, HD]
    k = k_ref[...].astype(jnp.float32).reshape(T * KH, HD)  # [T*KH, HD]
    v = v_ref[...].astype(jnp.float32).reshape(T * KH, HD)
    scale = HD ** -0.5

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # validity: own kv head, page exists, token offset < page_valid
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    own = (col % kv_heads) == (row // group)
    n_valid = page_valid_ref[b, i]
    exists = page_list_ref[b, i] >= 0
    valid = own & ((col // kv_heads) < n_valid) & exists
    s = jnp.where(valid, s, NEG_INF)

    # per-page lse (independent of running state -> numerically clean)
    m_p = jnp.max(s, axis=-1, keepdims=True)               # [R, 1]
    m_p_safe = jnp.where(m_p <= NEG_INF / 2, 0.0, m_p)
    p_loc = jnp.where(valid, jnp.exp(s - m_p_safe), 0.0)
    l_p = jnp.sum(p_loc, axis=-1, keepdims=True)           # [R, 1]
    lse_p = jnp.where(l_p > 0,
                      m_p_safe + jnp.log(jnp.maximum(l_p, 1e-37)),
                      NEG_INF)
    lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape, 1)
    lse_ref[...] = jnp.where(lane == i % LSE_LANES, lse_p, lse_ref[...])

    # running softmax update
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, m_p)
    m_new_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    corr_old = jnp.where(m_old <= NEG_INF / 2, 0.0,
                         jnp.exp(m_old - m_new_safe))
    corr_p = jnp.where(l_p > 0, jnp.exp(m_p_safe - m_new_safe), 0.0)
    pv = jax.lax.dot_general(p_loc, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [R, HD]
    l_scr[...] = l_scr[...] * corr_old + l_p * corr_p
    acc_scr[...] = acc_scr[...] * corr_old + pv * corr_p
    m_scr[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        l = l_scr[...]
        out_ref[...] = (acc_scr[...]
                        / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)
        m_out_ref[...] = m_scr[...]
        l_out_ref[...] = l


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_list: jax.Array, page_valid: jax.Array,
                    *, interpret: bool = True,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Semantics identical to `repro.kernels.ref.paged_attention_ref`.

    q: [B, KH, G, HD]; k_pool/v_pool: [B, P, T, KH, HD];
    page_list/page_valid: [B, N] int32. Returns (out [B, KH, G, HD],
    m, l [B, KH, G], page_lse [B, KH, G, N]).
    """
    B, KH, G, HD = q.shape
    T = k_pool.shape[2]
    N = page_list.shape[1]
    R = KH * G
    n_lse = -(-N // LSE_LANES) * LSE_LANES

    def q_map(b, i, pl_ref, pv_ref):
        return (b, 0, 0)

    def kv_map(b, i, pl_ref, pv_ref):
        slot = jnp.maximum(pl_ref[b, i], 0)   # clamp holes to page 0
        return (b, slot, 0, 0, 0)

    def lse_map(b, i, pl_ref, pv_ref):
        return (b, 0, i // LSE_LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N),
        in_specs=[
            pl.BlockSpec((None, R, HD), q_map),
            pl.BlockSpec((None, None, T, KH, HD), kv_map),
            pl.BlockSpec((None, None, T, KH, HD), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, R, HD), q_map),
            pl.BlockSpec((None, R, 1), q_map),
            pl.BlockSpec((None, R, 1), q_map),
            pl.BlockSpec((None, R, LSE_LANES), lse_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, HD), jnp.float32),
        ],
    )

    out_shapes = [
        jax.ShapeDtypeStruct((B, R, HD), q.dtype),
        jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, R, n_lse), jnp.float32),
    ]

    kernel = functools.partial(_kernel, kv_heads=KH, group=G)
    out, m, l, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="paged_attention",
    )(page_list, page_valid, q.reshape(B, R, HD), k_pool, v_pool)
    return (out.reshape(B, KH, G, HD), m.reshape(B, KH, G),
            l.reshape(B, KH, G), lse[:, :, :N].reshape(B, KH, G, N))
