"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import (NEG_INF, paged_attention,
                                           pages_per_block)


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


def _block(KH, HD, N, dtype):
    return pages_per_block(16, KH, HD, jnp.dtype(dtype).itemsize, N)


def _live_lists(rng, counts, N, P, T, holes):
    """[B, N] lists with counts[b] live entries a lane. Packed: the
    identity-or-hole layout `tier_lists` gives (live slots first, the
    lane's last page partly filled). With holes: live entries at random
    list positions, pointing at random slots, beside holes and occupied
    entries with no token (what migration swaps and Quest masks leave)."""
    B = len(counts)
    plist = np.full((B, N), -1, np.int32)
    pval = np.zeros((B, N), np.int32)
    for b, c in enumerate(counts):
        if holes:
            at = np.sort(rng.choice(N, c, replace=False))
            plist[b, at] = rng.choice(P, c, replace=False)
            pval[b, at] = rng.integers(1, T + 1, c)
            empty = np.setdiff1d(np.arange(N), at)[:2]
            plist[b, empty] = 0                  # a slot with no token
        else:
            plist[b, :c] = np.arange(c)
            pval[b, :c] = T
            if c:
                pval[b, c - 1] = T // 2 + 1
    return jnp.asarray(plist), jnp.asarray(pval)


#: (id, B, KH, G, HD, N, counts(K, N) per lane, holes): the compact walk's
#: edges, and the cells' tier sizes (internlm2 one chip: 32/240 and
#: 192/80 slots; the tp4 shard: 2 KV heads, 32/240)
WALK_CASES = [
    ("empty-beside-full", 3, 8, 2, 128, 32,
     lambda K, N: [N, 0, N], False),
    ("block-edges", 3, 8, 2, 128, 40,
     lambda K, N: [K - 1, K, K + 1], False),
    ("holes", 2, 8, 2, 128, 48, lambda K, N: [29, 11], True),
    ("ragged-n", 2, 8, 2, 128, 37, lambda K, N: [N, 21], False),
    ("two-lse-blocks", 1, 8, 2, 128, 136, lambda K, N: [101], True),
    ("spill-hbm", 2, 8, 2, 128, 32, lambda K, N: [N, 29], False),
    ("spill-host", 2, 8, 2, 128, 240, lambda K, N: [56, 3], False),
    ("chat-hbm", 2, 8, 2, 128, 192, lambda K, N: [61, 0], False),
    ("chat-host", 2, 8, 2, 128, 80, lambda K, N: [0, 5], False),
    ("tp4-hbm", 2, 2, 4, 128, 32, lambda K, N: [N, 30], False),
    ("tp4-host", 2, 2, 4, 128, 240, lambda K, N: [58, 0], True),
]


def _one_page_per_step(q, k_pool, v_pool, page_list, page_valid):
    """A grid step per list entry, holes included: each entry's scores,
    page softmax and running-softmax update, in list order -> (out, m,
    l). The arithmetic a kernel that visits every entry does."""
    B, KH, G, HD = q.shape
    T = k_pool.shape[2]
    R = KH * G
    row = jax.lax.broadcasted_iota(jnp.int32, (R, T * KH), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, T * KH), 1)
    own = (col % KH) == (row // G)

    def lane(qb, kb, vb, lb, nb):
        qf = qb.reshape(R, HD).astype(jnp.float32)

        def step(carry, x):
            m_old, l_old, acc_old = carry
            slot, n_valid = x
            k = kb[jnp.maximum(slot, 0)].astype(jnp.float32)
            v = vb[jnp.maximum(slot, 0)].astype(jnp.float32)
            s = jax.lax.dot_general(
                qf, k.reshape(T * KH, HD), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * HD ** -0.5
            valid = own & ((col // KH) < n_valid) & (slot >= 0)
            s = jnp.where(valid, s, NEG_INF)
            m_p = jnp.max(s, axis=-1, keepdims=True)
            m_p_safe = jnp.where(m_p <= NEG_INF / 2, 0.0, m_p)
            p_loc = jnp.where(valid, jnp.exp(s - m_p_safe), 0.0)
            l_p = jnp.sum(p_loc, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_old, m_p)
            m_new_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            corr_old = jnp.where(m_old <= NEG_INF / 2, 0.0,
                                 jnp.exp(m_old - m_new_safe))
            corr_p = jnp.where(l_p > 0, jnp.exp(m_p_safe - m_new_safe), 0.0)
            pv = jax.lax.dot_general(
                p_loc, v.reshape(T * KH, HD), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return (m_new, l_old * corr_old + l_p * corr_p,
                    acc_old * corr_old + pv * corr_p), None

        init = (jnp.full((R, 1), NEG_INF, jnp.float32),
                jnp.zeros((R, 1), jnp.float32),
                jnp.zeros((R, HD), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(step, init, (lb, nb))
        out = (acc / jnp.maximum(l, 1e-20)).astype(qb.dtype)
        return out.reshape(KH, G, HD), m.reshape(KH, G), l.reshape(KH, G)

    return jax.jit(jax.vmap(lane))(q, k_pool, v_pool, page_list,
                                   page_valid)


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

    @pytest.mark.parametrize("case", WALK_CASES, ids=[c[0] for c in WALK_CASES])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_compact_walk_matches_oracle(self, case, dtype):
        """The kernel walks only the live entries, in blocks: lanes with
        no page, counts at a block's edges, holes between live entries,
        ragged lists and the cells' tier shapes all match the oracle,
        with page LSEs back in list order."""
        name, B, KH, G, HD, N, counts, holes = case
        T, P = 16, N
        K = _block(KH, HD, N, dtype)
        rng = np.random.default_rng(sum(map(ord, name)))
        q, kp, vp, _, _ = _rand_paged(rng, B, KH, G, HD, P, T, 1, dtype)
        pl, pv = _live_lists(rng, counts(K, N), N, P, T, holes)
        o_r, m_r, l_r, lse_r = ref.paged_attention_ref(q, kp, vp, pl, pv)
        o_k, m_k, l_k, lse_k = paged_attention(q, kp, vp, pl, pv,
                                               interpret=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(o_k, np.float32),
                                   np.asarray(o_r, np.float32), atol=tol)
        np.testing.assert_allclose(m_k, m_r, atol=1e-4)
        np.testing.assert_allclose(l_k, l_r, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(lse_k, lse_r, atol=1e-3)

    @pytest.mark.parametrize("shape", [(3, 8, 2, 128, 40), (2, 2, 4, 128, 24),
                                       (2, 4, 2, 64, 9)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_identity_lists_match_one_page_per_step(self, shape, dtype):
        """On the identity-or-hole lists `tier_lists` gives, skipping the
        holes changes no bit of m or l against visiting every entry one
        page at a time: a hole is an exact no-op of the running softmax,
        and each live page keeps its arithmetic and order. out matches
        to an ulp of its dtype and 4 of float32: a block's P.V products
        do not depend on one another, and the CPU interpreter may
        compute the unrolled block's products as one batched dot, which
        sums in another order."""
        B, KH, G, HD, P = shape
        T = 16
        rng = np.random.default_rng(P)
        q, kp, vp, _, _ = _rand_paged(rng, B, KH, G, HD, P, T, 1, dtype)
        occupied = rng.uniform(size=(B, P)) < 0.5
        occupied[0] = False                     # a lane with no page
        pl = jnp.asarray(np.where(occupied, np.arange(P), -1), jnp.int32)
        pv = jnp.asarray(np.where(occupied, rng.integers(1, T + 1, (B, P)),
                                  0), jnp.int32)
        got = paged_attention(q, kp, vp, pl, pv, interpret=True)[:3]
        want = _one_page_per_step(q, kp, vp, pl, pv)
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
            rtol=float(jnp.finfo(dtype).eps),
            atol=4 * float(jnp.finfo(jnp.float32).eps))
        for g, w in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

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


#: (id, B, KH, G, C, T, Ph, Pe, starts, n_valid, mesh): prompt slices
#: of C tokens at lane offsets `starts` with `n_valid` real tokens.
#: tier-straddle: lane 0 crosses page 2 -> 3, the HBM/host boundary,
#: lane 1 starts at position 0, lane 2 does not prefill; partial-last:
#: slices shorter than C, one ending mid-page; many-blocks: walks of
#: several DMA blocks, one to the pool's last page; tp4-shard: granite's
#: per-chip heads (KH 2, G 4); sharded: under `ops.sharded_pools` on a
#: data=2, model=2 mesh of forced host devices (2 KV heads a shard)
PREFILL_CASES = [
    ("tier-straddle", 3, 8, 2, 16, 16, 3, 5, [40, 0, 20], [16, 16, 0],
     None),
    ("partial-last", 3, 2, 4, 16, 16, 2, 6, [33, 100, 0], [5, 12, 0], None),
    ("many-blocks", 3, 8, 2, 16, 16, 4, 12, [192, 240, 64], [16, 16, 7],
     None),
    ("tp4-shard", 2, 2, 4, 16, 16, 2, 14, [0, 200], [16, 9], None),
    ("sharded", 4, 4, 2, 16, 16, 3, 5, [40, 0, 7, 64], [16, 16, 0, 3],
     "2,2"),
]


def _prefill_check(case, dtype) -> None:
    """The paged prefill kernel (`ops.prefill_attention`, interpret
    mode) against the concatenated-pool path, on pools holding junk
    past each lane's prefix where the oracle's pools hold zeros: real
    rows match, padding rows and lanes with no prompt token are 0."""
    from repro.kernels import ops
    _, B, KH, G, C, T, Ph, Pe, starts, n_valid, mesh = case
    HD = 128
    rng = np.random.default_rng(sum(map(ord, case[0])))
    q = jnp.asarray(rng.standard_normal((B, C, KH * G, HD)), dtype)
    start = np.asarray(starts, np.int32)
    end = start + np.asarray(n_valid, np.int32)
    clean, junk = [], []
    for first, P in ((0, Ph), (0, Ph), (Ph, Pe), (Ph, Pe)):
        x = rng.standard_normal((B, P, T, KH, HD))
        pos = (first + np.arange(P))[:, None] * T + np.arange(T)[None]
        past = (pos[None] >= end[:, None, None])[..., None, None]
        clean.append(jnp.asarray(np.where(past, 0.0, x), dtype))
        junk.append(jnp.asarray(np.where(past, 8.0 * x + 4.0, x), dtype))
    pos = jnp.asarray(start[:, None] + np.arange(C)[None], jnp.int32)
    valid = np.arange(C)[None] < np.asarray(n_valid)[:, None]
    want = ops.prefill_attention(q, clean, pos, jnp.asarray(valid),
                                 use_pallas=False)

    def kernel(q, pools, pos, valid):
        return ops.prefill_attention(q, pools, pos, valid, use_pallas=True)

    if mesh is None:
        got = kernel(q, junk, pos, jnp.asarray(valid))
    else:
        from repro.kvcache.paged import CacheGeometry
        from repro.launch.mesh import make_test_mesh
        from repro.launch.shardings import pool_pspec
        data, model = map(int, mesh.split(","))
        mesh = make_test_mesh(data=data, model=model)
        geo = CacheGeometry(num_layers=1, batch=B, page_tokens=T,
                            hbm_pages=Ph, host_pages=Pe, kv_heads=KH,
                            head_dim=HD)
        spec = pool_pspec(geo, mesh)
        assert spec[3] == "model"

        def kernel_per_shard(*args):
            with ops.sharded_pools(mesh, spec):
                return kernel(*args)

        got = jax.jit(kernel_per_shard)(q, junk, pos, jnp.asarray(valid))
    got = np.asarray(got, np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got[valid],
                               np.asarray(want, np.float32)[valid], atol=tol)
    assert np.all(got[~valid] == 0.0)


class TestPagedPrefillKernel:
    @pytest.mark.parametrize("case", PREFILL_CASES,
                             ids=[c[0] for c in PREFILL_CASES])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_concatenated_pools(self, case, dtype):
        """Each prefilling lane's slice, over only the pages it holds,
        gives what the concatenated pools give; junk an earlier request
        left past a lane's prefix stays invisible."""
        if case[-1] is None:
            _prefill_check(case, dtype)
            return
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4")
        env["JAX_PLATFORMS"] = "cpu"
        here = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(here), "src"), here]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        proc = subprocess.run(
            [sys.executable, __file__, case[0], jnp.dtype(dtype).name],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stderr[-4000:]
        assert "PREFILL OK" in proc.stdout, proc.stdout


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


if __name__ == "__main__":
    # one PREFILL_CASES case in a process with forced host devices
    _case = {c[0]: c for c in PREFILL_CASES}[sys.argv[1]]
    _prefill_check(_case, jnp.dtype(sys.argv[2]))
    print("PREFILL OK")
