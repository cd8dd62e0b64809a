"""Operations and bytes that the served traffic needs, from sizes alone.

A dense decoder layer (RMSNorm, GQA attention with RoPE, SwiGLU) does
per token `2 * layer_params` matmul FLOPs and, at a context of `ctx`
keys, `4 * H * HD * ctx` attention FLOPs (scores and the weighted sum
of values). The output head adds `2 * D * V` where logits are needed:
every decode token, and the last prompt position of each request.

What counts is what the traffic needs, not what the program streams:
a decode lane reads the K/V of its live pages (its context rounded up
to whole pages), never the pool's holes, and a lane that is not
decoding reads nothing. So a kernel that skips holes shows as a gain
against the same work, and nothing here depends on `max_context` or
the pool's size.

The per-step counts come from the serve chunks' own outputs, recorded
by the harness (`ChunkRecord`): the cache length of each lane before
the chunk, and per step the tokens each lane emitted, the prompt tokens
it consumed and whether it sampled its first token.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, as a configuration file gives them."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool = False
    weight_bytes: int = 2
    kv_bytes: int = 2
    page_tokens: int = 16

    @staticmethod
    def from_config(c: Dict) -> "Dims":
        s = c["sizes"]
        return Dims(layers=s["num_layers"], d_model=s["d_model"],
                    heads=s["num_heads"], kv_heads=s["kv_heads"],
                    head_dim=s["head_dim"], d_ff=s["d_ff"],
                    vocab=s["vocab"], tied=s.get("tie_embeddings", False),
                    weight_bytes=DTYPE_BYTES[c["dtype"]],
                    kv_bytes=DTYPE_BYTES[c["dtype"]],
                    page_tokens=s.get("kv_page_tokens", 16))

    @property
    def layer_params(self) -> int:
        """Matmul weights of one layer: q, k, v, o and the SwiGLU."""
        d, h, kh, hd, f = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        return d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * f

    @property
    def step_weight_bytes(self) -> int:
        """Weights one forward step reads: every layer with its two
        norms, the final norm and the output head (the embedding table
        is gathered by row, which is not counted)."""
        d = self.d_model
        per_layer = self.layer_params + 2 * d
        return (self.layers * per_layer + d + d * self.vocab) \
            * self.weight_bytes

    @property
    def kv_token_bytes(self) -> int:
        """K and V of one token over every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim \
            * self.kv_bytes

    @property
    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def attn_flops(self, ctx) -> np.ndarray:
        """Attention FLOPs of one query at `ctx` keys, every layer."""
        return 4 * self.heads * self.head_dim * self.layers \
            * np.asarray(ctx, np.float64)

    def live_pages(self, ctx) -> np.ndarray:
        t = self.page_tokens
        return -(-np.asarray(ctx, np.int64) // t)


@dataclasses.dataclass
class ChunkRecord:
    """One serve chunk as its outputs report it: lengths [B] of each
    lane's cache before the chunk, and per step [S, B] the token each
    lane emitted (-1: none), its first token (-1: none) and the prompt
    tokens it consumed. `t0`/`t1` are the host clock (`time.time()`)
    around the chunk."""

    length0: np.ndarray
    emitted: np.ndarray
    first: np.ndarray
    prefill: np.ndarray
    t0: float = 0.0
    t1: float = 0.0

    def stamps(self) -> np.ndarray:
        """[S] host time of each step: t0 + (s + 1) / S of the span."""
        n = self.emitted.shape[0]
        return self.t0 + (np.arange(n) + 1) / n * (self.t1 - self.t0)

    def in_window(self, lo: Optional[float], hi: Optional[float]
                  ) -> np.ndarray:
        """[S] whether each step's stamp lies in (lo, hi]; None: open."""
        t = self.stamps()
        keep = np.ones(t.shape, bool)
        if lo is not None:
            keep &= t > lo
        if hi is not None:
            keep &= t <= hi
        return keep

    def tokens(self, lo: Optional[float] = None,
               hi: Optional[float] = None) -> int:
        """Tokens served (first tokens and decoded ones) in the window."""
        keep = self.in_window(lo, hi)
        return int((self.first[keep] >= 0).sum()
                   + (self.emitted[keep] >= 0).sum())


@dataclasses.dataclass
class Work:
    """Totals over chunks. `model_flops`/`step_bytes` are the whole
    step's needs (for `mfu`/`mbu`); `kernel_flops`/`kernel_bytes` are
    the decode attention's (for the paged kernel's roofline)."""

    model_flops: float = 0.0
    step_bytes: float = 0.0
    kernel_flops: float = 0.0
    kernel_bytes: float = 0.0
    decode_tokens: int = 0
    prefill_tokens: int = 0
    model_steps: int = 0
    decode_steps: int = 0

    def add(self, other: "Work") -> "Work":
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name)
                    + getattr(other, f.name))
        return self


def prefill_attn_flops(d: Dims, start, n) -> np.ndarray:
    """Causal attention FLOPs of `n` prompt tokens at positions
    start .. start + n - 1: the token at position p sees p + 1 keys."""
    start = np.asarray(start, np.float64)
    n = np.asarray(n, np.float64)
    keys = n * start + n * (n + 1) / 2
    return 4 * d.heads * d.head_dim * d.layers * keys


def chunk_work(d: Dims, rec: ChunkRecord, lo: Optional[float] = None,
               hi: Optional[float] = None) -> Work:
    """What the chunk's steps in the window (lo, hi] needed, step by
    step."""
    w = Work()
    length = np.asarray(rec.length0, np.int64).copy()
    keep = rec.in_window(lo, hi)
    for s in range(rec.emitted.shape[0]):
        dec = rec.emitted[s] >= 0
        n = np.asarray(rec.prefill[s], np.int64)
        pf = n > 0
        crossed = rec.first[s] >= 0
        if not keep[s] or not (dec.any() or pf.any()):
            length[dec] += 1
            length[pf] += n[pf]
            continue
        w.model_steps += 1
        w.step_bytes += d.step_weight_bytes
        if dec.any():
            # the step writes the lane's newest token, then attends
            # over every cached token including it
            ctx = length[dec] + 1
            w.decode_steps += 1
            w.decode_tokens += int(dec.sum())
            kf = float(d.attn_flops(ctx).sum())
            kb = float((d.live_pages(ctx) * d.page_tokens).sum()
                       * d.kv_token_bytes)
            w.kernel_flops += kf
            w.kernel_bytes += kb
            w.model_flops += kf + float(dec.sum()) * (
                2 * d.layers * d.layer_params + d.head_flops)
            w.step_bytes += kb
        if pf.any():
            start, k = length[pf], n[pf]
            w.prefill_tokens += int(k.sum())
            w.model_flops += float(k.sum()) * 2 * d.layers * d.layer_params
            w.model_flops += float(prefill_attn_flops(d, start, k).sum())
            # the slice reads its prefix's pages and writes its own
            w.step_bytes += float((d.live_pages(start + k) * d.page_tokens
                                   ).sum() * d.kv_token_bytes)
        w.model_flops += float(crossed.sum()) * d.head_flops
        length[dec] += 1
        length[pf] += n[pf]
    return w


def total_work(d: Dims, records: Iterable[ChunkRecord],
               lo: Optional[float] = None,
               hi: Optional[float] = None) -> Work:
    w = Work()
    for rec in records:
        w.add(chunk_work(d, rec, lo, hi))
    return w
