"""Seeded traffic for the benchmark, from the parameters of a traffic file.

Copied from the program's workload plane (`benchmarks/workloads.py`):
Poisson, bursty on-off and diurnal arrivals (the latter two by
Lewis-Shedler thinning) and uniform or lognormal lengths. Adapted so
the yardstick imports nothing of the program: a stream is plain
arrays, and the harness turns it into requests.

Every seed of one traffic file gets the same set of prompt lengths,
output lengths and Poisson gaps, in another order: the sizes are the
distribution's quantiles at (i + 0.5) / n, and the seed only permutes
them and draws the prompt tokens. So the work a window holds depends
little on the seed, and two seeds differ by order, not by load. A mix
whose window holds too few requests for that to even out fixes the
order too (`trace_seed`): each run replays one trace of sizes and
arrival times, and the run's seed draws the prompt tokens.

    spec = TrafficSpec.from_json(json.load(open("bench/traffic/x.json")))
    stream = generate(spec, seed=7, seconds=30.0, vocab=92544)
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Callable, Dict, List, Optional

import numpy as np

ARRIVALS = ("poisson", "bursty", "diurnal")
LENGTHS = ("uniform", "lognormal")


@dataclasses.dataclass(frozen=True)
class LengthSpec:
    """A token-length distribution: `uniform` over [lo, hi], or
    `lognormal` with the given median and sigma, clipped to [lo, hi]."""

    dist: str
    lo: int
    hi: int
    median: float = 0.0
    sigma: float = 0.0

    def __post_init__(self):
        if self.dist not in LENGTHS:
            raise ValueError(f"length dist {self.dist!r} not in {LENGTHS}")
        if not 1 <= self.lo <= self.hi:
            raise ValueError(f"need 1 <= lo <= hi, got {self.lo}, {self.hi}")
        if self.dist == "lognormal" and (self.median <= 0 or self.sigma <= 0):
            raise ValueError("lognormal lengths need median > 0, sigma > 0")

    def quantile(self, u: np.ndarray) -> np.ndarray:
        """The length at each probability in `u` (0 < u < 1)."""
        if self.dist == "uniform":
            x = self.lo + np.floor(u * (self.hi - self.lo + 1))
        else:
            z = np.asarray([statistics.NormalDist().inv_cdf(float(p))
                            for p in u])
            x = np.rint(self.median * np.exp(self.sigma * z))
        return np.clip(x, self.lo, self.hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class TrafficSpec:
    """One traffic mix. `loop` is `closed` (all `n_requests` due at
    the window's start; the lanes stay full while the queue lasts) or
    `open` (arrivals at `rate_rps` over the window, whether or not the
    server keeps up). A closed loop with `block` (the lanes, say) draws
    its sizes in blocks of that many requests, each spanning the whole
    law (`_blocked`). With `trace_seed`, every seed replays the same
    sizes and arrivals and differs only in its prompt tokens.

    `warm_s` is served before the window opens, so that the window
    sees the lanes at staggered points of their requests rather than
    all starting at once; a stream covers `warm_s` plus the window.
    A `staggered` closed loop speeds that up: its first block, one
    request a lane, joins as if part-way through, request k with
    (k + 0.5) / block of its drawn answer left, so the lanes free up
    one by one rather than together."""

    loop: str
    prompt: LengthSpec
    output: LengthSpec
    n_requests: int = 0
    arrival: str = "poisson"
    rate_rps: float = 0.0
    burst_factor: float = 4.0
    on_fraction: float = 0.25
    off_level: float = 0.25
    period_s: float = 1.0
    diurnal_amp: float = 0.8
    block: int = 0
    trace_seed: Optional[int] = None
    warm_s: float = 0.0
    staggered: bool = False

    def __post_init__(self):
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop {self.loop!r} is neither closed nor open")
        if self.loop == "closed" and self.n_requests < 1:
            raise ValueError("a closed loop needs n_requests >= 1")
        if self.block and (self.loop != "closed"
                           or self.n_requests % self.block):
            raise ValueError("block needs a closed loop whose n_requests "
                             "it divides")
        if self.staggered and not self.block:
            raise ValueError("staggered needs a closed loop in blocks")
        if self.loop == "open":
            if self.arrival not in ARRIVALS:
                raise ValueError(f"arrival {self.arrival!r} not in {ARRIVALS}")
            if self.rate_rps <= 0:
                raise ValueError("an open loop needs rate_rps > 0")

    @staticmethod
    def from_json(d: Dict) -> "TrafficSpec":
        """The `traffic` section of a traffic file."""
        d = dict(d)
        d["prompt"] = LengthSpec(**d["prompt"])
        d["output"] = LengthSpec(**d["output"])
        return TrafficSpec(**d)

    def count(self, seconds: float) -> int:
        """Requests in one window of `seconds`."""
        if self.loop == "closed":
            return self.n_requests
        return max(1, int(round(self.rate_rps * seconds)))


@dataclasses.dataclass
class Stream:
    """A materialised stream: per-request arrays, arrival-ordered.
    `arrival_s` is 0 for every request of a closed loop."""

    arrival_s: np.ndarray          # [n] float64, ascending
    prompt_len: np.ndarray         # [n] int64
    max_new: np.ndarray            # [n] int64
    prompts: List[np.ndarray]      # [n] int32 token rows

    @property
    def n(self) -> int:
        return len(self.prompts)


# ---------------------------------------------------------------------------
# samplers


def _thin(rng: np.random.Generator, lam: Callable[[float], float],
          lam_max: float, n: int) -> np.ndarray:
    """Lewis-Shedler thinning: a homogeneous Poisson stream at
    `lam_max`, each point kept with probability lam(t)/lam_max."""
    out = np.empty(n, np.float64)
    got, t = 0, 0.0
    while got < n:
        t += rng.exponential(1.0 / lam_max)
        if rng.random() * lam_max <= lam(t):
            out[got] = t
            got += 1
    return out


def _arrivals(rng: np.random.Generator, spec: TrafficSpec,
              n: int) -> np.ndarray:
    """Bursty or diurnal arrival times, by thinning."""
    rate = spec.rate_rps
    if spec.arrival == "bursty":
        hi = rate * spec.burst_factor
        lo = rate * spec.off_level

        def lam(t: float) -> float:
            phase = (t % spec.period_s) / spec.period_s
            return hi if phase < spec.on_fraction else lo

        return _thin(rng, lam, hi, n)
    amp = min(spec.diurnal_amp, 0.999)

    def lam(t: float) -> float:
        return rate * (1.0 + amp * np.sin(2.0 * np.pi * t / spec.period_s))

    return _thin(rng, lam, rate * (1.0 + amp), n)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def _blocked(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """The probabilities of `n` strata in blocks of `block` requests in
    a row: block k holds every (n / block)-th stratum from the k-th, so
    each block spans the whole law, and the seed permutes within each
    block. A closed loop serves its first blocks first, so every seed
    serves nearly the same sizes in a window."""
    nb = n // block
    u = _strata(n)
    return np.concatenate([rng.permutation(u[k::nb]) for k in range(nb)])


# ---------------------------------------------------------------------------
# the stream


def generate(spec: TrafficSpec, seed: int, seconds: float, vocab: int,
             limit: Optional[int] = None) -> Stream:
    """One (spec, seed, window) -> one stream, bitwise. Draw order:
    prompt lengths, output lengths, arrivals, prompt tokens.

    Poisson arrivals: the gaps are the exponential law's
    quantiles, permuted, and scaled so that the last of n requests is
    due at seconds * n / (n + 1). `limit` caps every prompt + output
    (the cache's capacity per lane): a pair over it loses output
    tokens.

    With the spec's `trace_seed`, the sizes and arrivals are drawn from
    that seed instead: every run replays one trace of sizes and arrival
    times, and `seed` draws only the prompt tokens."""
    rng = np.random.default_rng(seed)
    shape = rng if spec.trace_seed is None \
        else np.random.default_rng(spec.trace_seed)
    n = spec.count(seconds)
    if spec.block:
        plen = spec.prompt.quantile(_blocked(shape, n, spec.block))
        olen = spec.output.quantile(_blocked(shape, n, spec.block))
    else:
        u = _strata(n)
        plen = shape.permutation(spec.prompt.quantile(u))
        olen = shape.permutation(spec.output.quantile(u))
    if spec.loop == "closed":
        arrival = np.zeros(n, np.float64)
    elif spec.arrival == "poisson":
        gaps = shape.permutation(-np.log1p(-_strata(n)))
        arrival = np.cumsum(gaps)
        arrival *= seconds * n / (n + 1) / arrival[-1]
    else:
        arrival = _arrivals(shape, spec, n)
    if spec.staggered:
        k = spec.block
        olen[:k] = np.maximum(np.rint(olen[:k] * _strata(k)), 1)
    if limit is not None:
        olen = np.maximum(np.minimum(olen, limit - plen), 1)
    prompts = [rng.integers(0, vocab, int(k)).astype(np.int32) for k in plen]
    return Stream(arrival_s=arrival, prompt_len=plen, max_new=olen,
                  prompts=prompts)
