"""Public jit'd wrappers around the Pallas kernels.

`tiered_paged_attention` is the two-tier composition the whole serving
stack uses: per-tier paged attention (Pallas kernel on TPU, pure-jnp
oracle on CPU) merged exactly via log-sum-exp — the TPU-idiomatic form
of the paper's concurrent HBM/DRAM reads (Eq. 2's max(t_h, t_e) becomes
two overlapped kernel invocations whose partials merge associatively).

Backend selection: `use_pallas=None` auto-picks the kernel on TPU and
the reference on CPU (interpret-mode Pallas is used by tests, not by
the hot path — it is Python-slow).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_attention


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: (mesh, one layer's pool PartitionSpec) while a meshed program traces
_SHARDED_POOLS = contextvars.ContextVar("sharded_pools", default=None)


@contextlib.contextmanager
def sharded_pools(mesh, pool_spec):
    """Trace `tier_attention`'s Pallas kernel per shard of `mesh`.

    GSPMD cannot partition a Mosaic kernel, so a meshed program that
    takes the kernel is traced inside this context. `pool_spec` is the
    layout of one layer's `[B, P, T, KH, HD]` pool, as the program's
    owner placed it (`launch.shardings.pool_pspec`); queries and the
    page lists follow its lane and head axes."""
    token = _SHARDED_POOLS.set((mesh, pool_spec))
    try:
        yield
    finally:
        _SHARDED_POOLS.reset(token)


def tier_attention(q, k_pool, v_pool, page_list, page_valid,
                   *, use_pallas: Optional[bool] = None):
    """Partial attention over one tier -> (out, m, l, page_lse).

    TPU: the Pallas paged kernel (page-table gather in SMEM), run per
    shard under `shard_map` inside `sharded_pools`.
    Otherwise: the gather-free dense pool form — page_list from
    `tier_lists` is identity-or-hole, holes already have valid == 0,
    so masking alone is exact, and GSPMD keeps pools page-sharded.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.pool_attention_ref(q, k_pool, v_pool, page_valid)
    kernel = functools.partial(paged_attention, interpret=not _on_tpu())
    layout = _SHARDED_POOLS.get()
    if layout is None:
        return kernel(q, k_pool, v_pool, page_list, page_valid)
    mesh, pool = layout
    lanes, pages, _, heads, _ = pool
    if pages is not None:
        raise NotImplementedError(
            "the Pallas paged kernel needs each shard to hold whole "
            "page ranges; page-sharded pools (kv_heads not divisible by "
            "the model axis) need a cross-shard LSE merge")
    qs = P(lanes, heads, None, None)
    ml = P(lanes, heads, None)
    lane = P(lanes, None)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(qs, pool, pool, lane, lane),
        out_specs=(qs, ml, ml, qs), check_vma=False)(
            q, k_pool, v_pool, page_list, page_valid)


def tiered_paged_attention(
    q: jax.Array,
    k_hbm: jax.Array, v_hbm: jax.Array,
    k_host: jax.Array, v_host: jax.Array,
    hbm_list: jax.Array, hbm_valid: jax.Array,
    host_list: jax.Array, host_valid: jax.Array,
    *, use_pallas: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Decode attention over the union of two tiers.

    q: [B, KH, G, HD]. Returns (out [B, KH, G, HD], importance [B, Nh+Ne])
    where importance is the per-page attention mass (summed over heads),
    ordered [hbm pages..., host pages...] matching the two lists.
    """
    out_h, m_h, l_h, lse_h = tier_attention(
        q, k_hbm, v_hbm, hbm_list, hbm_valid, use_pallas=use_pallas)
    out_e, m_e, l_e, lse_e = tier_attention(
        q, k_host, v_host, host_list, host_valid, use_pallas=use_pallas)
    merged, total_lse = ref.merge_partials(
        [(out_h, m_h, l_h), (out_e, m_e, l_e)])
    imp_h = ref.page_importance(lse_h, total_lse)
    imp_e = ref.page_importance(lse_e, total_lse)
    return merged.astype(q.dtype), jnp.concatenate([imp_h, imp_e], axis=-1)


def flash_attention(q, k, v, *, causal: bool = True,
                    use_pallas: Optional[bool] = None,
                    q_block: int = 256, k_block: int = 256) -> jax.Array:
    """Prefill/train attention, public layout [B, S, H, D]."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, q_block=q_block,
                               k_block=k_block, interpret=not _on_tpu())
    return out.transpose(0, 2, 1, 3)


def pool_layout():
    """`(mesh, pool_spec)` inside `sharded_pools`, else None."""
    return _SHARDED_POOLS.get()
