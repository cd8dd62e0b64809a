"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_attention


def _rand_paged(rng, B, KH, G, HD, P, T, N, dtype):
    q = jnp.asarray(rng.standard_normal((B, KH, G, HD)), dtype)
    kp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), dtype)
    pl = jnp.asarray(rng.integers(-1, P, (B, N)), jnp.int32)
    pv = jnp.asarray(rng.integers(0, T + 1, (B, N)), jnp.int32)
    return q, kp, vp, pl, pv


PAGED_SHAPES = [
    # (B, KH, G, HD, P, T, N)
    (1, 1, 1, 64, 4, 16, 4),
    (2, 4, 2, 128, 8, 16, 6),
    (2, 2, 8, 128, 16, 16, 16),   # qwen3-like G=8
    (1, 8, 1, 64, 8, 16, 8),      # zamba2-like MHA
    (3, 2, 5, 128, 8, 16, 5),     # llama4-like G=5
    (2, 8, 2, 128, 12, 16, 12),   # internlm2-1.8b widths (the chip's layout)
    (1, 8, 2, 128, 8, 16, 136),   # page LSEs span two 128-page blocks
]


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("shape", PAGED_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, shape, dtype):
        B, KH, G, HD, P, T, N = shape
        rng = np.random.default_rng(hash(shape) % 2**31)
        q, kp, vp, pl, pv = _rand_paged(rng, B, KH, G, HD, P, T, N, dtype)
        o_r, m_r, l_r, lse_r = ref.paged_attention_ref(q, kp, vp, pl, pv)
        o_k, m_k, l_k, lse_k = paged_attention(q, kp, vp, pl, pv,
                                               interpret=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(o_k, np.float32),
                                   np.asarray(o_r, np.float32), atol=tol)
        np.testing.assert_allclose(m_k, m_r, atol=1e-4)
        np.testing.assert_allclose(l_k, l_r, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(lse_k, lse_r, atol=1e-3)

    def test_all_holes(self):
        """A tier with nothing resident: l == 0, out finite."""
        rng = np.random.default_rng(0)
        q, kp, vp, _, _ = _rand_paged(rng, 2, 2, 2, 64, 4, 16, 4,
                                      jnp.float32)
        pl = jnp.full((2, 4), -1, jnp.int32)
        pv = jnp.zeros((2, 4), jnp.int32)
        o, m, l, lse = paged_attention(q, kp, vp, pl, pv, interpret=True)
        assert np.all(np.asarray(l) == 0.0)
        assert np.all(np.isfinite(np.asarray(o)))

    def test_sharded_kernel_under_mesh_context(self):
        """`ops.tier_attention` runs the kernel under shard_map inside
        `ops.sharded_pools` (as the engine's meshed serve jit does):
        same results as the oracle."""
        from repro.kernels import ops
        from repro.launch.mesh import make_test_mesh
        from repro.launch.shardings import pool_pspec
        from repro.kvcache.paged import CacheGeometry
        mesh = make_test_mesh(data=1, model=1)
        B, KH, G, HD, P, T = 2, 4, 2, 64, 6, 16
        geo = CacheGeometry(num_layers=1, batch=B, page_tokens=T,
                            hbm_pages=P, host_pages=P, kv_heads=KH,
                            head_dim=HD)
        rng = np.random.default_rng(2)
        q, kp, vp, pl, pv = _rand_paged(rng, B, KH, G, HD, P, T, P,
                                        jnp.float32)

        def f(*args):
            with ops.sharded_pools(mesh, pool_pspec(geo, mesh)):
                return ops.tier_attention(*args, use_pallas=True)

        got = jax.jit(f)(q, kp, vp, pl, pv)
        want = ref.paged_attention_ref(q, kp, vp, pl, pv)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4)

    def test_sharded_kernel_refuses_page_sharded_pools(self):
        """Each shard's kernel walks whole page ranges: a pool layout
        with the model axis on pages is refused at trace time."""
        from jax.sharding import PartitionSpec
        from repro.kernels import ops
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(data=1, model=1)
        rng = np.random.default_rng(4)
        args = _rand_paged(rng, 2, 2, 2, 64, 4, 16, 4, jnp.float32)
        pages = PartitionSpec(None, "model", None, None, None)
        with ops.sharded_pools(mesh, pages):
            with pytest.raises(NotImplementedError):
                jax.jit(functools.partial(
                    ops.tier_attention, use_pallas=True))(*args)

    def test_pool_attention_matches_identity_paged(self):
        """Gather-free SPMD path == paged oracle with identity layout."""
        rng = np.random.default_rng(1)
        B, KH, G, HD, P, T = 2, 4, 2, 64, 8, 16
        q, kp, vp, _, _ = _rand_paged(rng, B, KH, G, HD, P, T, P,
                                      jnp.float32)
        valid = jnp.asarray(rng.integers(0, T + 1, (B, P)), jnp.int32)
        plist = jnp.where(valid > 0, jnp.arange(P, dtype=jnp.int32)[None],
                          jnp.int32(-1))
        o1, m1, l1, lse1 = ref.paged_attention_ref(q, kp, vp, plist, valid)
        o2, m2, l2, lse2 = ref.pool_attention_ref(q, kp, vp, valid)
        np.testing.assert_allclose(o1, o2, atol=1e-5)
        np.testing.assert_allclose(lse1, lse2, atol=1e-4)


class TestTierMerge:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_two_tier_merge_equals_single_pool(self, seed):
        """Splitting pages across two tiers + LSE merge == one big pool."""
        rng = np.random.default_rng(seed)
        B, KH, G, HD, T = 1, 2, 2, 32, 8
        P = 6
        q = jnp.asarray(rng.standard_normal((B, KH, G, HD)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), jnp.float32)
        valid = jnp.asarray(rng.integers(1, T + 1, (B, P)), jnp.int32)

        # single pool
        o_all, m_all, l_all, _ = ref.pool_attention_ref(q, kp, vp, valid)

        # split: first 2 pages tier A, rest tier B
        cut = 2
        oa = ref.pool_attention_ref(q, kp[:, :cut], vp[:, :cut],
                                    valid[:, :cut])
        ob = ref.pool_attention_ref(q, kp[:, cut:], vp[:, cut:],
                                    valid[:, cut:])
        merged, lse = ref.merge_partials([oa[:3], ob[:3]])
        np.testing.assert_allclose(np.asarray(merged),
                                   np.asarray(o_all), atol=1e-5)

    def test_merge_associativity(self):
        rng = np.random.default_rng(7)
        B, KH, G, HD, T, P = 1, 1, 1, 16, 16, 9
        q = jnp.asarray(rng.standard_normal((B, KH, G, HD)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((B, P, T, KH, HD)), jnp.float32)
        valid = jnp.full((B, P), T, jnp.int32)
        parts = [ref.pool_attention_ref(q, kp[:, i:i+3], vp[:, i:i+3],
                                        valid[:, i:i+3])[:3]
                 for i in (0, 3, 6)]
        m1, _ = ref.merge_partials(parts)
        # merge in a different association order
        a, _ = ref.merge_partials(parts[:2])
        # merge_partials needs (out, m, l); recompute m,l for merged pair
        o_all, m_all, l_all, _ = ref.pool_attention_ref(
            q, kp[:, :6], vp[:, :6], valid[:, :6])
        m2, _ = ref.merge_partials([(o_all, m_all, l_all), parts[2]])
        np.testing.assert_allclose(np.asarray(m1), np.asarray(m2),
                                   atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,S,D,qb,kb", [
        (1, 1, 128, 64, 64, 64),
        (2, 3, 256, 64, 128, 64),
        (1, 2, 512, 128, 128, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, B, H, S, D, qb, kb, dtype, causal):
        rng = np.random.default_rng(B * 100 + S)
        q = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
        k = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
        v = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
        out = flash_attention_bhsd(q, k, v, causal=causal, q_block=qb,
                                   k_block=kb, interpret=True)
        oref = ref.flash_attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal).transpose(0, 2, 1, 3)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(oref, np.float32), atol=tol)

    def test_flash_jnp_chunked_matches_naive(self):
        from repro.models.layers import flash_attention_jnp, naive_attention
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((2, 256, 4, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 256, 4, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 256, 4, 32)), jnp.float32)
        a = flash_attention_jnp(q, k, v, causal=True, q_chunk=64, k_chunk=64)
        b = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
