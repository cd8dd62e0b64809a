"""Whether the served tokens are right: the comparison that decides
`correct`.

Once the window has closed and the program's state is freed, a sample
of the requests the window served (finished, or cut by the window's
end with the tokens they had), drawn from the seed and holding the one
with the most served tokens, is run through the plain reference: one
forward over each prompt with its served tokens. At the position of
each served token, the gap is how far that token's reference logit
lies below the reference's best. Decoding is greedy,
so a served token is the program's argmax, and the gap is zero unless
the program's rounding flipped a near tie. The number compared is the
widest gap.

The control puts the reference in the program's place at the precision
below the configuration's (`lowp`): at the same positions its own
argmax is read against the float32 reference in the same way.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Sample:
    prompt: np.ndarray        # [P] int32
    output: np.ndarray        # [N] int32, N >= 1


def draw(finished: Sequence[Sample], seed: int, tokens: int,
         max_requests: int) -> List[Sample]:
    """The request with the most served tokens, then others in an order
    drawn from `seed`, until `tokens` served tokens or `max_requests`."""
    if not finished:
        return []
    n_out = np.asarray([len(s.output) for s in finished])
    n_in = np.asarray([len(s.prompt) for s in finished])
    first = int(np.lexsort((n_in, n_out))[-1])
    rng = np.random.default_rng([seed, 2])
    rest = [i for i in rng.permutation(len(finished)) if i != first]
    out, got = [], 0
    for i in [first] + rest:
        if got >= tokens or len(out) >= max_requests:
            break
        out.append(finished[i])
        got += len(finished[i].output)
    return out


def _padded(s: Sample, seq_len: int, n_pos: int):
    seq = np.concatenate([s.prompt, s.output[:-1]]).astype(np.int32)
    if len(seq) > seq_len or len(s.output) > n_pos:
        raise ValueError(f"request of {len(seq)} tokens / {len(s.output)} "
                         f"served exceeds the check's {seq_len} / {n_pos}")
    toks = np.zeros(seq_len, np.int32)
    toks[:len(seq)] = seq
    pos = np.zeros(n_pos, np.int32)
    n = len(s.output)
    pos[:n] = len(s.prompt) - 1 + np.arange(n)
    return toks, pos, n


def reference_module(family: str):
    return importlib.import_module(f"bench.reference.{family}")


def gaps(family: str, sizes: Dict, weights, samples: Sequence[Sample],
         seq_len: int, n_pos: int, lowp: Optional[str] = None) -> Dict:
    """Widest gap of the served tokens (and, with `lowp`, of the
    control's argmax) below the float32 reference's best logit."""
    ref = reference_module(family)
    fwd = ref.make_forward(sizes, seq_len, n_pos)
    ctl = ref.make_forward(sizes, seq_len, n_pos, lowp=lowp) if lowp else None
    served, control, count = [], [], 0
    for s in samples:
        toks, pos, n = _padded(s, seq_len, n_pos)
        want = np.asarray(fwd(weights, jnp.asarray(toks), jnp.asarray(pos)),
                          np.float64)[:n]
        best = want.max(axis=-1)
        got = want[np.arange(n), s.output]
        served.append(float((best - got).max()))
        if ctl is not None:
            low = np.asarray(ctl(weights, jnp.asarray(toks),
                                 jnp.asarray(pos)), np.float64)[:n]
            pick = low.argmax(axis=-1)
            control.append(float((best - want[np.arange(n), pick]).max()))
        count += n
    out = {"logit_gap": max(served) if served else None,
           "tokens": count, "requests": len(samples)}
    if ctl is not None:
        out["control_gap"] = max(control) if control else None
    return out
