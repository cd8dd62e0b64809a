"""Decoder-only + encoder-decoder transformer families.

Covers: internlm2 / granite / qwen3 (qk_norm) / stablelm (dense GQA),
internvl2 (VLM = dense backbone over [patch_embeds; token_embeds]),
whisper (enc-dec with stubbed conv frontend), and the attention blocks
of the MoE and hybrid families (moe.py / ssm.py reuse `attn_qkv` etc.).

All layer stacks are `lax.scan`s over stacked parameters: compile time
and HLO size are depth-independent, which is what makes the 64-layer /
512-device dry-runs tractable, and the remat policy wraps the scan body.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kvcache.paged import (
    IMPORTANCE_EMA, PagedKVCache, allocate_prompt_pages, no_write_slot,
    write_token_layer, write_tokens_layer,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope, attention, constrain_batch, layer_norm,
    repeat_kv, rms_norm, swiglu,
)
from repro.models.params import Param


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def attn_schema(cfg: ModelConfig, L: int, prefix_axes=("layers",)):
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    Lax = prefix_axes
    Ld = (L,) if L else ()
    s = {
        "attn_norm": Param(Ld + (d,), Lax + ("embed",), "ones"),
        "wq": Param(Ld + (d, h, hd), Lax + ("embed", "heads", "head_dim"),
                    fan_in_axes=(len(Ld),)),
        "wk": Param(Ld + (d, kh, hd), Lax + ("embed", "kv_heads", "head_dim"),
                    fan_in_axes=(len(Ld),)),
        "wv": Param(Ld + (d, kh, hd), Lax + ("embed", "kv_heads", "head_dim"),
                    fan_in_axes=(len(Ld),)),
        "wo": Param(Ld + (h, hd, d), Lax + ("heads", "head_dim", "embed"),
                    fan_in_axes=(len(Ld), len(Ld) + 1)),
    }
    if cfg.qk_norm:
        s["q_norm"] = Param(Ld + (hd,), Lax + ("head_dim",), "ones")
        s["k_norm"] = Param(Ld + (hd,), Lax + ("head_dim",), "ones")
    return s


def mlp_schema(cfg: ModelConfig, L: int, prefix_axes=("layers",)):
    d, f = cfg.d_model, cfg.d_ff
    Ld = (L,) if L else ()
    Lax = prefix_axes
    return {
        "mlp_norm": Param(Ld + (d,), Lax + ("embed",), "ones"),
        "w_gate": Param(Ld + (d, f), Lax + ("embed", "mlp"),
                        fan_in_axes=(len(Ld),)),
        "w_up": Param(Ld + (d, f), Lax + ("embed", "mlp"),
                      fan_in_axes=(len(Ld),)),
        "w_down": Param(Ld + (f, d), Lax + ("mlp", "embed"),
                        fan_in_axes=(len(Ld),)),
    }


def dense_schema(cfg: ModelConfig):
    L = cfg.num_layers
    s = {
        "embed": Param((cfg.vocab, cfg.d_model), ("vocab", "embed"), "embed"),
        "final_norm": Param((cfg.d_model,), ("embed",), "ones"),
        "layers": {**attn_schema(cfg, L), **mlp_schema(cfg, L)},
    }
    if not cfg.tie_embeddings:
        s["unembed"] = Param((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                             fan_in_axes=(0,))
    return s


def encdec_schema(cfg: ModelConfig):
    """Whisper-style: LN+bias, GELU MLP, learned positions, cross-attn."""
    d, f = cfg.d_model, cfg.d_ff
    Le = cfg.encdec.enc_layers
    Ld = cfg.num_layers

    def ln(L):
        return {
            "w": Param((L, d), ("layers", "embed"), "ones"),
            "b": Param((L, d), ("layers", "embed"), "zeros"),
        }

    def attn(L):
        base = attn_schema(cfg, L)
        del base["attn_norm"]
        return base

    def mlp(L):
        return {
            "w_in": Param((L, d, f), ("layers", "embed", "mlp"),
                          fan_in_axes=(1,)),
            "b_in": Param((L, f), ("layers", "mlp"), "zeros"),
            "w_out": Param((L, f, d), ("layers", "mlp", "embed"),
                           fan_in_axes=(1,)),
            "b_out": Param((L, d), ("layers", "embed"), "zeros"),
        }

    return {
        "embed": Param((cfg.vocab, d), ("vocab", "embed"), "embed"),
        "dec_pos": Param((cfg.encdec.dec_positions, d),
                         (None, "embed"), "embed"),
        "enc_pos": Param((cfg.encdec.enc_positions, d), (None, "embed"),
                         "embed"),
        "enc_layers": {
            "ln1": ln(Le), "attn": attn(Le), "ln2": ln(Le), "mlp": mlp(Le),
        },
        "enc_final": {"w": Param((d,), ("embed",), "ones"),
                      "b": Param((d,), ("embed",), "zeros")},
        "dec_layers": {
            "ln1": ln(Ld), "self_attn": attn(Ld),
            "ln2": ln(Ld), "cross_attn": attn(Ld),
            "ln3": ln(Ld), "mlp": mlp(Ld),
        },
        "dec_final": {"w": Param((d,), ("embed",), "ones"),
                      "b": Param((d,), ("embed",), "zeros")},
    }


# ---------------------------------------------------------------------------
# Forward building blocks
# ---------------------------------------------------------------------------

def attn_qkv(x, lp, cfg: ModelConfig, positions, rope: bool = True):
    """x [B,S,d] -> q [B,S,H,HD], k/v [B,S,KH,HD] (RoPE applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def full_attn_block(h, lp, cfg: ModelConfig, positions, *, causal=True,
                    collect_kv=False):
    """Pre-norm attention block over a full sequence (train/prefill)."""
    h = constrain_batch(h)
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    q, k, v = attn_qkv(x, lp, cfg, positions)
    kr = repeat_kv(k, cfg.q_per_kv)
    vr = repeat_kv(v, cfg.q_per_kv)
    o = attention(q, kr, vr, causal=causal)
    h = h + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    return (h, (k, v)) if collect_kv else (h, None)


def dense_mlp_block(h, lp, cfg: ModelConfig):
    h = constrain_batch(h)
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def dense_layer(h, lp, cfg: ModelConfig, positions, collect_kv=False):
    h, kv = full_attn_block(h, lp, cfg, positions, collect_kv=collect_kv)
    h = dense_mlp_block(h, lp, cfg)
    return h, kv


# ---------------------------------------------------------------------------
# Dense decoder: forward (train / prefill) and paged decode step
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    return params["embed"][tokens].astype(cfg.dtype)


def unembed(params, cfg: ModelConfig, h):
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return jnp.einsum("bsd,dv->bsv", h, w)


def dense_forward(params, cfg: ModelConfig, tokens, *,
                  input_embeds: Optional[jax.Array] = None,
                  collect_kv: bool = False, remat: bool = True):
    """tokens [B,S] (or input_embeds [B,S,d]) -> logits [B,S,V].

    collect_kv additionally returns post-RoPE (k, v) stacked [L,B,S,KH,HD]
    for prefill cache population.
    """
    h = embed_tokens(params, cfg, tokens) if input_embeds is None \
        else input_embeds
    B, S = h.shape[0], h.shape[1]
    positions = jnp.arange(S)[None, :]

    def body(carry, lp):
        out, kv = dense_layer(carry, lp, cfg, positions,
                              collect_kv=collect_kv)
        return out, kv

    if remat:
        body = jax.checkpoint(body)
    h, kvs = jax.lax.scan(body, h, params["layers"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, h)
    return (logits, kvs) if collect_kv else logits


def allocate_token_page(cache: PagedKVCache,
                        write_slot: jax.Array) -> PagedKVCache:
    """Register the logical page receiving this step's token in the page
    table / owner maps (MUST run before tier_lists so the fresh page is
    visible to the attention kernel). A lane whose write slot is NO_WRITE
    (`paged.no_write_slot`) registers nothing: every index it takes is
    out of range and dropped."""
    import dataclasses as dc
    L, B = write_slot.shape
    hbm_pages = cache.k_hbm.shape[2]
    host_pages = cache.k_host.shape[2]
    T = cache.k_hbm.shape[3]
    max_pages = cache.page_table.shape[2]
    logical = jnp.minimum(cache.length // T, max_pages - 1)   # [B]
    logical = jnp.broadcast_to(logical[None, :], (L, B))
    lidx = jnp.arange(L)[:, None]
    bidx = jnp.arange(B)[None, :]
    writes = write_slot < no_write_slot(cache)
    page = jnp.where(writes, logical, max_pages)
    page_table = cache.page_table.at[lidx, bidx, page].set(
        write_slot, mode="drop")
    hslot = jnp.where(write_slot < hbm_pages, write_slot, hbm_pages)
    hbm_owner = cache.hbm_owner.at[lidx, bidx, hslot].set(
        logical, mode="drop")
    eslot = jnp.where(write_slot >= hbm_pages, write_slot - hbm_pages,
                      host_pages)                 # NO_WRITE: host_pages
    host_owner = cache.host_owner.at[lidx, bidx, eslot].set(
        logical, mode="drop")
    return dc.replace(cache, page_table=page_table, hbm_owner=hbm_owner,
                      host_owner=host_owner)


def mask_write_visible(cache: PagedKVCache, logical_page_mask):
    """Quest masks must never hide the page receiving this step's token
    (the step's own K/V lands there and attention must see it). Returns
    the mask with the current logical page forced visible, or None.
    Shared by every cache-backed decode path (dense/vlm/moe/hybrid/
    encdec)."""
    if logical_page_mask is None:
        return None
    B = cache.length.shape[0]
    T = cache.k_hbm.shape[3]
    logical = jnp.minimum(cache.length // T, cache.page_table.shape[2] - 1)
    return logical_page_mask.at[..., jnp.arange(B), logical].set(True)


def dense_decode_step(params, cfg: ModelConfig, cache: PagedKVCache,
                      token: jax.Array, write_slot: jax.Array,
                      use_pallas: Optional[bool] = None,
                      logical_page_mask: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, PagedKVCache]:
    """One decode step over the two-tier paged cache.

    token: [B] int32. write_slot: [L, B] physical slot receiving this
    token's page (chosen by the control plane; slot >= hbm_pages means
    host pool). logical_page_mask enables Quest-style token bypassing
    (False pages are not read). Returns (logits [B, V], updated cache).
    """
    B = token.shape[0]
    T = cache.k_hbm.shape[3]
    pos = cache.length                        # [B]
    offset = pos % T
    h = embed_tokens(params, cfg, token[:, None])    # [B,1,d]

    cache = allocate_token_page(cache, write_slot)
    logical_page_mask = mask_write_visible(cache, logical_page_mask)
    hl, hv, el, ev = cache.tier_lists(
        logical_page_mask=logical_page_mask)  # [L,B,P*]

    # the pools ride the carry and each layer's slice is written back in
    # place: as scan outputs they would be fresh buffers, which the
    # serve chunk's decode branch then copies whole into its result
    def body(carry, xs):
        hcur, pools = carry
        l, lp, slot, hl_l, hv_l, el_l, ev_l = xs
        k_hbm_l, v_hbm_l, k_host_l, v_host_l = (
            jax.lax.dynamic_index_in_dim(p, l, keepdims=False)
            for p in pools)
        x = rms_norm(hcur, lp["attn_norm"], cfg.norm_eps)
        q, k, v = attn_qkv(x, lp, cfg, pos[:, None])
        # write this token's k/v BEFORE attending (it must see itself)
        k_hbm_l, v_hbm_l, k_host_l, v_host_l = write_token_layer(
            k_hbm_l, v_hbm_l, k_host_l, v_host_l, slot, offset,
            k[:, 0], v[:, 0])
        # GQA grouped layout [B, KH, G, HD]
        qg = q[:, 0].reshape(B, cfg.kv_heads, cfg.q_per_kv, cfg.head_dim)
        # the freshly written token must be visible: recompute valid
        # counts with length+1
        hv_new = _bump_valid(hv_l, slot, offset, T, hbm=True,
                             hbm_pages=k_hbm_l.shape[1])
        ev_new = _bump_valid(ev_l, slot - k_hbm_l.shape[1], offset, T,
                             hbm=False, hbm_pages=k_hbm_l.shape[1])
        o, imp = ops.tiered_paged_attention(
            qg, k_hbm_l, v_hbm_l, k_host_l, v_host_l,
            hl_l, hv_new, el_l, ev_new, use_pallas=use_pallas)
        o = o.reshape(B, 1, cfg.num_heads, cfg.head_dim)
        hcur = hcur + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        hcur = dense_mlp_block(hcur, lp, cfg)
        pools = tuple(
            jax.lax.dynamic_update_index_in_dim(p, new, l, 0)
            for p, new in zip(pools, (k_hbm_l, v_hbm_l, k_host_l,
                                      v_host_l)))
        return (hcur, pools), imp

    pools = (cache.k_hbm, cache.v_hbm, cache.k_host, cache.v_host)
    xs = (jnp.arange(write_slot.shape[0]), params["layers"], write_slot,
          hl, hv, el, ev)
    (h, (k_hbm, v_hbm, k_host, v_host)), imp = jax.lax.scan(
        body, (h, pools), xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, h)[:, 0]

    cache = _update_cache_after_step(cache, k_hbm, v_hbm, k_host, v_host,
                                     imp, write_slot, offset)
    return logits, cache


def _bump_valid(valid, slot, offset, T, *, hbm: bool, hbm_pages: int):
    """Account for the token written this step in the tier valid counts
    (none for a NO_WRITE slot, which lies past the host tier)."""
    B = valid.shape[0]
    in_tier = (slot < hbm_pages) if hbm else (
        (slot >= 0) & (slot < valid.shape[1]))
    s = jnp.clip(slot, 0, valid.shape[1] - 1)
    bidx = jnp.arange(B)
    bumped = valid.at[bidx, s].set(
        jnp.where(in_tier, jnp.maximum(valid[bidx, s], offset + 1),
                  valid[bidx, s]))
    return bumped


def _update_cache_after_step(cache, k_hbm, v_hbm, k_host, v_host, imp,
                             write_slot, offset):
    """Fold the step's pool updates + importance stats back into the
    cache (tables were already updated by allocate_token_page)."""
    import dataclasses as dc
    L, B = write_slot.shape
    max_pages = cache.page_table.shape[2]
    lidx = jnp.arange(L)[:, None]
    bidx = jnp.arange(B)[None, :]

    # importance: EMA over per-page attention mass. imp is [L, B, Ph+Pe]
    # in tier-slot order; scatter back to logical pages via owners.
    ema = IMPORTANCE_EMA
    owner = jnp.concatenate([cache.hbm_owner, cache.host_owner], axis=2)
    owner_safe = jnp.clip(owner, 0, max_pages - 1)
    mass = jnp.zeros_like(cache.importance)
    mass = mass.at[lidx[..., None], bidx[..., None], owner_safe].add(
        jnp.where(owner >= 0, imp, 0.0))
    importance = (1 - ema) * cache.importance + ema * mass

    return dc.replace(cache, k_hbm=k_hbm, v_hbm=v_hbm, k_host=k_host,
                      v_host=v_host, length=cache.length + 1,
                      importance=importance)


# ---------------------------------------------------------------------------
# Chunked prefill (Sarathi-style) into the paged cache at an offset
# ---------------------------------------------------------------------------

def prefill_chunk_attn(hcur, lp, cfg: ModelConfig, pools, pos, page,
                       offset, valid):
    """One layer's chunked-prefill attention block over the paged pools.

    hcur: [B, C, d] residual stream for a prompt slice; pools:
    (k_hbm_l, v_hbm_l, k_host_l, v_host_l); pos/page/offset/valid:
    [B, C] absolute positions and their page coordinates. Writes the
    slice's K/V at static-placement slots (slot == logical page), then
    attends causally against the pools (`ops.prefill_attention`) in
    slot order — which IS logical token order while the lane is
    prefilling, because the migration planner only touches lanes that
    have started decoding. Shared by the dense and moe chunked-prefill
    forwards.
    """
    kh, vh, ke, ve = pools
    hcur = constrain_batch(hcur)
    x = rms_norm(hcur, lp["attn_norm"], cfg.norm_eps)
    q, k, v = attn_qkv(x, lp, cfg, pos)
    kh, vh, ke, ve = write_tokens_layer(kh, vh, ke, ve, page, offset,
                                        k, v, valid)
    o = ops.prefill_attention(q, (kh, vh, ke, ve), pos, valid)
    hcur = hcur + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    return hcur, (kh, vh, ke, ve)


def chunk_coords(page_tokens: int, chunk: int, start: jax.Array,
                 n_valid: jax.Array):
    """Page coordinates for a `chunk`-token slice at lane offsets
    `start` [B] with `n_valid` [B] real tokens: (pos, page, offset,
    valid), all [B, C]."""
    pos = start[:, None] + jnp.arange(chunk, dtype=start.dtype)[None, :]
    valid = jnp.arange(chunk)[None, :] < n_valid[:, None]
    page = (pos // page_tokens).astype(jnp.int32)
    offset = (pos % page_tokens).astype(jnp.int32)
    return pos, page, offset, valid


def dense_prefill_chunk(params, cfg: ModelConfig, cache: PagedKVCache,
                        tokens: jax.Array, start: jax.Array,
                        n_valid: jax.Array
                        ) -> Tuple[jax.Array, PagedKVCache]:
    """Consume a [B, C] prompt slice directly into the paged cache.

    Token j of lane b sits at absolute position start[b] + j and is
    real while j < n_valid[b] (the rest of the slice is padding and is
    neither written nor trusted). K/V pages are written at an offset
    under static placement — no batch-1 side cache, no per-length
    compiles: C is the only traced shape, lane offsets are data.
    Returns (logits [B, C, V], updated cache); the logits at slice
    index n_valid-1 are those of the last consumed prompt position, so
    the first output token can be sampled on device at the step where
    prefill crosses prompt_len.

    Bitwise invariant off the TPU (pinned by
    tests/test_chunked_prefill.py): for the valid positions this
    reproduces `dense_forward` exactly, at ANY chunk budget —
    per-position ops are shape-invariant and `prefix_chunk_attention`
    sees the identical visible prefix. On the TPU the paged prefill
    kernel's online softmax matches it to bf16 rounding.
    """
    C = tokens.shape[1]
    T = cache.k_hbm.shape[3]
    pos, page, offset, valid = chunk_coords(T, C, start, n_valid)
    h = embed_tokens(params, cfg, tokens)

    def body(carry, xs):
        lp, kh, vh, ke, ve = xs
        hcur, pools = prefill_chunk_attn(carry, lp, cfg, (kh, vh, ke, ve),
                                         pos, page, offset, valid)
        hcur = dense_mlp_block(hcur, lp, cfg)
        return hcur, pools

    xs = (params["layers"], cache.k_hbm, cache.v_hbm, cache.k_host,
          cache.v_host)
    h, (kh, vh, ke, ve) = jax.lax.scan(body, h, xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, h)
    import dataclasses as dc
    cache = dc.replace(cache, k_hbm=kh, v_hbm=vh, k_host=ke, v_host=ve)
    cache = allocate_prompt_pages(cache, pos, valid, n_valid)
    return logits, cache


# ---------------------------------------------------------------------------
# Encoder-decoder (whisper)
# ---------------------------------------------------------------------------

def _ln(x, p, eps):
    return layer_norm(x, p["w"], p["b"], eps)


def _encdec_attn(x_q, x_kv, lp, cfg, *, causal):
    q = jnp.einsum("bsd,dhk->bshk", x_q, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x_kv, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_kv, lp["wv"])
    kr = repeat_kv(k, cfg.q_per_kv)
    vr = repeat_kv(v, cfg.q_per_kv)
    o = attention(q, kr, vr, causal=causal)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"])


def encoder_forward(params, cfg: ModelConfig, frames: jax.Array,
                    remat: bool = True):
    """frames: [B, F, d] precomputed frame embeddings (conv stub)."""
    F = frames.shape[1]
    h = (frames.astype(cfg.dtype)
         + params["enc_pos"][:F][None].astype(cfg.dtype))

    def body(carry, lp):
        carry = constrain_batch(carry)
        x = _ln(carry, lp["ln1"], cfg.norm_eps)
        carry = carry + _encdec_attn(x, x, lp["attn"], cfg, causal=False)
        x = _ln(carry, lp["ln2"], cfg.norm_eps)
        m = jnp.einsum("bsd,df->bsf", x, lp["mlp"]["w_in"]) + lp["mlp"]["b_in"]
        carry = carry + (jnp.einsum("bsf,fd->bsd", jax.nn.gelu(m),
                                    lp["mlp"]["w_out"]) + lp["mlp"]["b_out"])
        return carry, None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return _ln(h, params["enc_final"], cfg.norm_eps)


def encdec_forward(params, cfg: ModelConfig, tokens, enc_embeds,
                   remat: bool = True, collect_kv: bool = False):
    """Teacher-forced decode over encoder output. tokens [B,S]."""
    enc = encoder_forward(params, cfg, enc_embeds, remat=remat)
    S = tokens.shape[1]
    h = (params["embed"][tokens]
         + params["dec_pos"][:S][None]).astype(cfg.dtype)

    def body(carry, lp):
        carry = constrain_batch(carry)
        x = _ln(carry, lp["ln1"], cfg.norm_eps)
        sa = lp["self_attn"]
        k = jnp.einsum("bsd,dhk->bshk", x, sa["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, sa["wv"])
        carry = carry + _encdec_attn(x, x, sa, cfg, causal=True)
        x = _ln(carry, lp["ln2"], cfg.norm_eps)
        carry = carry + _encdec_attn(x, enc, lp["cross_attn"], cfg,
                                     causal=False)
        x = _ln(carry, lp["ln3"], cfg.norm_eps)
        m = jnp.einsum("bsd,df->bsf", x, lp["mlp"]["w_in"]) + lp["mlp"]["b_in"]
        carry = carry + (jnp.einsum("bsf,fd->bsd", jax.nn.gelu(m),
                                    lp["mlp"]["w_out"]) + lp["mlp"]["b_out"])
        return carry, ((k, v) if collect_kv else None)

    if remat:
        body = jax.checkpoint(body)
    h, kvs = jax.lax.scan(body, h, params["dec_layers"])
    h = _ln(h, params["dec_final"], cfg.norm_eps)
    logits = unembed(params, cfg, h)
    return (logits, kvs, enc) if collect_kv else logits
