"""Seeded workload plane: realistic open-loop traffic for the serve loop.

Serving results are only as honest as the traffic behind them. A
uniform closed-loop stream (same prompt length, greedy, all submitted
at t=0) hides exactly the effects the paper's heterogeneous-memory
placement is about: queueing under bursts, heavy-tailed prompt
footprints competing for device pages, and latency SLOs that goodput
is scored against. This module generates that traffic DETERMINISTICALLY
from one integer seed — two instantiations of the same `WorkloadSpec`
produce bitwise-identical arrival times, lengths, tiers, prompt tokens
and sampling keys (tests/test_workloads.py pins this), so every
benchmark row and property test replays exactly.

Pieces (EXPERIMENTS.md §Workloads):

  * arrival processes — homogeneous Poisson (exponential gaps), bursty
    on-off and diurnal sinusoid, the latter two via Lewis-Shedler
    thinning against the rate envelope's maximum;
  * length samplers — lognormal body mixed with a truncated-Zipf tail
    (`zipf_frac`), a fraction snapped UP to page boundaries
    (`snap_frac`, chunked-ingest prompts that exactly fill KV pages —
    the cache-geometry edge case);
  * priority tiers — weighted draw over `TierSpec`s; `SLOPolicy` maps
    tier names to TTFT/TPOT targets (`repro.serving.slo`);
  * sampled traffic — a per-stream `SamplingConfig` plus a drawn
    `stream_seed` for `serve(seed=...)`, so non-greedy streams are as
    reproducible as greedy ones.

`Workload.requests()` materialises `repro.serving.scheduler.Request`
objects with `arrival_s` stamped for the engine's open-loop driver:
`ServingEngine.serve` submits each one at the first chunk boundary
whose wall clock passes its offset. The arrival pattern is pure data —
all three processes drive ONE serve executable (zero retraces), which
tests/test_workloads.py pins.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.sampling import SamplingConfig
from repro.serving.scheduler import Request

#: the default priority mix: mostly interactive chat, some standard
#: API traffic, a batch tail that tolerates queueing
DEFAULT_TIERS = (("interactive", 0.6), ("standard", 0.3), ("batch", 0.1))

ARRIVALS = ("poisson", "bursty", "diurnal")


@dataclasses.dataclass(frozen=True)
class TierSpec:
    """One priority tier: its name (the `Request.tier` /
    `SLOPolicy.targets` key) and its share of the traffic."""

    name: str
    weight: float


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Everything that defines a stream; hashable, seed included —
    the spec IS the workload identity."""

    seed: int = 0
    n_requests: int = 32
    #: arrival process ("poisson" | "bursty" | "diurnal") + mean rate
    arrival: str = "poisson"
    rate_rps: float = 50.0
    #: bursty on-off envelope: rate*burst_factor for the first
    #: `on_fraction` of every `period_s`, rate*off_level otherwise
    burst_factor: float = 4.0
    on_fraction: float = 0.25
    off_level: float = 0.25
    period_s: float = 1.0
    #: diurnal envelope: rate * (1 + amp * sin(2*pi*t/period_s))
    diurnal_amp: float = 0.8
    #: prompt lengths: lognormal(mu, sigma) body mixed with a
    #: truncated Zipf(alpha) tail over [1, max_prompt]
    len_mu: float = 3.0
    len_sigma: float = 0.8
    zipf_alpha: float = 1.3
    zipf_frac: float = 0.25
    min_prompt: int = 1
    max_prompt: int = 96
    #: fraction of prompts snapped UP to a page boundary
    page_tokens: int = 16
    snap_frac: float = 0.25
    #: decode budgets: lognormal clipped to [1, max_new]
    out_mu: float = 2.2
    out_sigma: float = 0.6
    max_new: int = 24
    #: priority mix ((name, weight), ...)
    tiers: Tuple[Tuple[str, float], ...] = DEFAULT_TIERS
    #: sampled (non-greedy) traffic knobs; temperature 0 = greedy
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    #: vocabulary the prompt tokens are drawn from (match the model)
    vocab: int = 256

    def __post_init__(self):
        assert self.arrival in ARRIVALS, self.arrival
        assert self.n_requests >= 1 and self.rate_rps > 0
        assert 0.0 <= self.zipf_frac <= 1.0
        assert 0.0 <= self.snap_frac <= 1.0
        assert abs(sum(w for _, w in self.tiers) - 1.0) < 1e-9, \
            "tier weights must sum to 1"


# ---------------------------------------------------------------------------
# samplers


def zipf_cdf(alpha: float, support: int) -> np.ndarray:
    """CDF of the truncated Zipf(alpha) law over ranks 1..support.
    Exposed so the KS property test scores `sample_zipf` against the
    exact distribution it inverts."""
    pmf = np.arange(1, support + 1, dtype=np.float64) ** (-alpha)
    pmf /= pmf.sum()
    return np.cumsum(pmf)


def sample_zipf(rng: np.random.Generator, alpha: float, support: int,
                size: int) -> np.ndarray:
    """Truncated-Zipf draw by inverse CDF (searchsorted): exact, no
    rejection, one uniform per sample — bitwise reproducible."""
    cdf = zipf_cdf(alpha, support)
    u = rng.random(size)
    return np.searchsorted(cdf, u, side="left") + 1


def _thin(rng: np.random.Generator, lam: Callable[[float], float],
          lam_max: float, n: int) -> np.ndarray:
    """Lewis-Shedler thinning: draw a homogeneous Poisson stream at
    `lam_max` and keep each point with probability lam(t)/lam_max —
    an exact sampler for any bounded-rate inhomogeneous process."""
    out = np.empty(n, np.float64)
    got, t = 0, 0.0
    while got < n:
        t += rng.exponential(1.0 / lam_max)
        if rng.random() * lam_max <= lam(t):
            out[got] = t
            got += 1
    return out


def _arrivals(rng: np.random.Generator, spec: WorkloadSpec) -> np.ndarray:
    n, rate = spec.n_requests, spec.rate_rps
    if spec.arrival == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, n))
    if spec.arrival == "bursty":
        hi = rate * spec.burst_factor
        lo = rate * spec.off_level

        def lam(t: float) -> float:
            phase = (t % spec.period_s) / spec.period_s
            return hi if phase < spec.on_fraction else lo

        return _thin(rng, lam, hi, n)
    # diurnal sinusoid; amp < 1 keeps the rate positive
    amp = min(spec.diurnal_amp, 0.999)

    def lam(t: float) -> float:
        return rate * (1.0 + amp * np.sin(2.0 * np.pi * t / spec.period_s))

    return _thin(rng, lam, rate * (1.0 + amp), n)


def _lengths(rng: np.random.Generator, spec: WorkloadSpec) -> np.ndarray:
    """Lognormal body + Zipf tail, page-boundary snapping."""
    n = spec.n_requests
    body = rng.lognormal(spec.len_mu, spec.len_sigma, n)
    tail = sample_zipf(rng, spec.zipf_alpha, spec.max_prompt, n)
    use_tail = rng.random(n) < spec.zipf_frac
    out = np.where(use_tail, tail, np.rint(body))
    out = np.clip(out, spec.min_prompt, spec.max_prompt).astype(np.int64)
    snap = rng.random(n) < spec.snap_frac
    pt = max(1, spec.page_tokens)
    snapped = np.minimum(-(-out // pt) * pt, spec.max_prompt)
    return np.maximum(np.where(snap, snapped, out), 1)


# ---------------------------------------------------------------------------
# the generated stream


@dataclasses.dataclass
class Workload:
    """A materialised stream: parallel per-request arrays plus the
    stream-level sampling contract. `requests()` builds fresh
    `Request` objects each call (the engine mutates them)."""

    spec: WorkloadSpec
    arrival_s: np.ndarray          # [n] float64, sorted ascending
    prompt_len: np.ndarray         # [n] int64, >= 1
    max_new: np.ndarray            # [n] int64, >= 1
    tier: List[str]                # [n]
    prompts: List[np.ndarray]      # [n] int32 token rows
    stream_seed: int               # for serve(seed=...)
    sampling: SamplingConfig

    @property
    def n(self) -> int:
        return len(self.prompts)

    def requests(self, *, start_rid: int = 0, time_scale: float = 1.0,
                 open_loop: bool = True) -> List[Request]:
        """Materialise the stream. `time_scale` stretches/compresses
        the arrival clock (0.1 = 10x faster replay); `open_loop=False`
        drops the arrival stamps entirely (everything submits at t=0,
        the closed-loop baseline)."""
        out = []
        for i in range(self.n):
            out.append(Request(
                rid=start_rid + i,
                prompt=self.prompts[i],
                max_new_tokens=int(self.max_new[i]),
                arrival_s=float(self.arrival_s[i]) * time_scale
                if open_loop else 0.0,
                tier=self.tier[i]))
        return out

    def serve_kwargs(self) -> dict:
        """The stream's sampling contract for `ServingEngine.serve`."""
        return {"seed": self.stream_seed, "sampling": self.sampling}


def generate(spec: WorkloadSpec) -> Workload:
    """One seed -> one stream, bitwise. Draw ORDER is part of the
    contract (arrivals, prompt lengths, decode budgets, tiers, stream
    seed, prompt tokens): changing it changes every downstream
    benchmark row, so treat it like a wire format."""
    rng = np.random.default_rng(spec.seed)
    arrival = _arrivals(rng, spec)
    plen = _lengths(rng, spec)
    decode = np.clip(np.rint(rng.lognormal(spec.out_mu, spec.out_sigma,
                                           spec.n_requests)),
                     1, spec.max_new).astype(np.int64)
    names = [t[0] for t in spec.tiers]
    weights = np.asarray([t[1] for t in spec.tiers], np.float64)
    tier_ix = rng.choice(len(names), size=spec.n_requests,
                         p=weights / weights.sum())
    stream_seed = int(rng.integers(0, 2**31 - 1))
    prompts = [rng.integers(0, spec.vocab, int(n)).astype(np.int32)
               for n in plen]
    return Workload(
        spec=spec, arrival_s=arrival, prompt_len=plen, max_new=decode,
        tier=[names[i] for i in tier_ix], prompts=prompts,
        stream_seed=stream_seed,
        sampling=SamplingConfig(temperature=spec.temperature,
                                top_k=spec.top_k, top_p=spec.top_p))


def merge(parts: Sequence[Workload],
          stream_seed: Optional[int] = None) -> Workload:
    """Superpose streams into one arrival-sorted stream (e.g. the
    bench's mixed Poisson+bursty row). Sampling contract comes from
    the first part; pass `stream_seed` to override."""
    assert parts, "merge needs at least one workload"
    arrival = np.concatenate([w.arrival_s for w in parts])
    order = np.argsort(arrival, kind="stable")
    plen = np.concatenate([w.prompt_len for w in parts])[order]
    decode = np.concatenate([w.max_new for w in parts])[order]
    tiers = np.asarray(sum((w.tier for w in parts), []))[order]
    prompts = [p for w in parts for p in w.prompts]
    return Workload(
        spec=parts[0].spec, arrival_s=arrival[order], prompt_len=plen,
        max_new=decode, tier=list(tiers),
        prompts=[prompts[i] for i in order],
        stream_seed=parts[0].stream_seed
        if stream_seed is None else stream_seed,
        sampling=parts[0].sampling)


def mixed_stream(seed: int, n_requests: int, **overrides) -> Workload:
    """The bench's canonical mixed stream: half Poisson, half bursty,
    superposed — steady load with burst waves on top."""
    half = max(1, n_requests // 2)
    a = generate(WorkloadSpec(seed=seed, n_requests=half,
                              arrival="poisson", **overrides))
    b = generate(WorkloadSpec(seed=seed + 1, n_requests=n_requests - half,
                              arrival="bursty", **overrides))
    return merge([a, b], stream_seed=a.stream_seed)


def drive(engine, workload: Workload, *, time_scale: float = 1.0,
          **serve_kwargs):
    """Open-loop load driver sugar: submit the stream against its
    wall-clock arrival offsets with its own sampling contract."""
    reqs = workload.requests(time_scale=time_scale)
    kw = workload.serve_kwargs()
    kw.update(serve_kwargs)
    return engine.serve(reqs, **kw)
