"""Plain float32 forward of the dense decoder family.

Pre-norm decoder layers: RMSNorm, grouped-query attention with rotary
position embedding (the half-split rotation), a residual add, RMSNorm,
a SwiGLU MLP, a residual add; then a final RMSNorm and the output head
(untied, or the embedding's transpose where tied). Query head `h` reads
key/value head `h // (heads / kv_heads)`.

Straightforward `jax.numpy` in float32 with every matmul at
`Precision.HIGHEST`: no kernel, no cache, no batching. The weights are
upcast one layer at a time inside a scan over layers, and attention is
computed in blocks of query rows, so a whole sequence of several
thousand tokens fits beside the served weights.

`lowp="fp8"` is the benchmark's control: the same forward with every
matmul operand rounded to float8 (e4m3) under a scale per row or
column of its contraction, accumulating in float32 — the precision
below the bfloat16 the configurations state.

    fwd = make_forward(sizes, seq_len=4096, n_pos=512)
    logits = fwd(weights, tokens, positions)      # [n_pos, vocab] f32
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _lowp(x: jax.Array, axes, lowp: Optional[str]) -> jax.Array:
    """Round `x` to the control's precision, one scale per slice
    along the contraction `axes`."""
    if lowp is None:
        return x
    if lowp != "fp8":
        raise ValueError(f"unknown low precision {lowp!r}")
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec: str, a, b, a_axes, b_axes, lowp):
    return jnp.einsum(spec, _lowp(a, a_axes, lowp), _lowp(b, b_axes, lowp),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, N, HD]: rotate the first half against the second."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_forward(sizes: Dict, seq_len: int, n_pos: int,
                 lowp: Optional[str] = None, q_block: int = 512):
    """A jitted `(weights, tokens [seq_len], positions [n_pos]) ->
    logits [n_pos, vocab]` for one sequence. Tokens past the sequence's
    end may hold anything: attention is causal, so they change no
    earlier position."""
    H, KH, HD = sizes["num_heads"], sizes["kv_heads"], sizes["head_dim"]
    G = H // KH
    eps = sizes.get("norm_eps", 1e-5)
    theta = sizes.get("rope_theta", 1e6)
    tied = sizes.get("tie_embeddings", False)
    S = seq_len
    qb = min(q_block, S)
    if S % qb:
        raise ValueError(f"seq_len {S} is not a multiple of {qb}")
    nb = S // qb
    scale = HD ** -0.5

    def attention(q, k, v):
        # q [S, KH, G, HD]; k, v [S, KH, HD]
        kq = _lowp(k, -1, lowp)
        vq = _lowp(v, 0, lowp)
        kpos = jnp.arange(S)

        def block(i):
            qblk = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
            s = jnp.einsum("qkgd,skd->kgqs", _lowp(qblk, -1, lowp), kq,
                           precision=HIGHEST) * scale
            qpos = i * qb + jnp.arange(qb)
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqs,skd->qkgd", _lowp(p, -1, lowp), vq,
                              precision=HIGHEST)

        out = jax.lax.map(block, jnp.arange(nb))      # [nb, qb, KH, G, HD]
        return out.reshape(S, H, HD)

    def layer(h, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        pos = jnp.arange(S)
        x = _rms(h, lw["attn_norm"], eps)
        q = _einsum("sd,dhk->shk", x, lw["wq"], -1, 0, lowp)
        k = _einsum("sd,dhk->shk", x, lw["wk"], -1, 0, lowp)
        v = _einsum("sd,dhk->shk", x, lw["wv"], -1, 0, lowp)
        q = _rope(q, pos, theta).reshape(S, KH, G, HD)
        k = _rope(k, pos, theta)
        o = attention(q, k, v)
        h = h + _einsum("shk,hkd->sd", o, lw["wo"], (1, 2), (0, 1), lowp)
        x = _rms(h, lw["mlp_norm"], eps)
        g = _einsum("sd,df->sf", x, lw["w_gate"], -1, 0, lowp)
        u = _einsum("sd,df->sf", x, lw["w_up"], -1, 0, lowp)
        a = jax.nn.silu(g) * u
        h = h + _einsum("sf,fd->sd", a, lw["w_down"], -1, 0, lowp)
        return h, None

    def forward(weights, tokens, positions):
        with jax.default_matmul_precision("highest"):
            h = weights["embed"][tokens].astype(jnp.float32)
            h, _ = jax.lax.scan(layer, h, weights["layers"])
            hs = _rms(h[positions], weights["final_norm"], eps)
            head = (weights["embed"].T if tied else weights["unembed"])
            return _einsum("sd,dv->sv", hs, head.astype(jnp.float32),
                           -1, 0, lowp)

    return jax.jit(forward)
