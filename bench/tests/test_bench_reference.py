"""The plain reference against the program's prefill-then-decode.

At smoke widths on the CPU, in float32, the reference's logits at every
position equal the served model's: the prefill's last position, then
each decode step over the paged cache with the previous token fed in.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import dense
from bench.tests import smoke
from repro import configs
from repro.models.model import Model


def _model(dtype):
    base = configs.get_smoke("internlm2-1.8b")
    return Model(dataclasses.replace(base, **smoke.SIZES, dtype=dtype,
                                     param_dtype=dtype))


def _program_logits(model, w, tokens, prompt):
    geo = model.cache_geometry(1, 256, hbm_fraction=0.25)
    prefill = jax.jit(model.prefill, static_argnums=(2,))
    step = jax.jit(model.decode_step)
    last, cache = prefill(w, jnp.asarray(tokens[None, :prompt]), geo)
    out = [np.asarray(last[0], np.float64)]
    for t in tokens[prompt:]:
        logits, cache = step(w, cache, jnp.asarray([t]))
        out.append(np.asarray(logits[0], np.float64))
    return np.stack(out)


def _reference_logits(sizes, w, tokens, prompt):
    n = len(tokens) - prompt + 1
    fwd = dense.make_forward(sizes, seq_len=128, n_pos=32)
    toks = np.zeros(128, np.int32)
    toks[:len(tokens)] = tokens
    pos = np.zeros(32, np.int32)
    pos[:n] = prompt - 1 + np.arange(n)
    return np.asarray(fwd(w, jnp.asarray(toks), jnp.asarray(pos)),
                      np.float64)[:n]


def test_reference_matches_prefill_then_decode_float32():
    sizes = dict(smoke.SIZES, rope_theta=1e6, norm_eps=1e-5)
    w = weights.make(sizes, "float32", seed=11)
    tokens = np.random.default_rng(0).integers(0, 256, 70).astype(np.int32)
    want = _reference_logits(sizes, w, tokens, prompt=50)
    with jax.default_matmul_precision("highest"):
        got = _program_logits(_model(jnp.float32), w, tokens, prompt=50)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_reference_tracks_the_bf16_program():
    """The served bf16 model lies within bf16 rounding of the float32
    reference: a tenth of the logits' spread."""
    sizes = dict(smoke.SIZES, rope_theta=1e6, norm_eps=1e-5)
    w = weights.make(sizes, "bfloat16", seed=12)
    tokens = np.random.default_rng(1).integers(0, 256, 60).astype(np.int32)
    want = _reference_logits(sizes, w, tokens, prompt=40)
    got = _program_logits(_model(jnp.bfloat16), w, tokens, prompt=40)
    assert np.abs(got - want).max() < 0.1 * want.std()


def test_reference_is_causal_over_padding():
    sizes = dict(smoke.SIZES)
    w = weights.make(sizes, "float32", seed=13)
    fwd = dense.make_forward(sizes, seq_len=64, n_pos=4)
    toks = np.random.default_rng(2).integers(0, 256, 64).astype(np.int32)
    pos = jnp.asarray([3, 10, 20, 29], jnp.int32)
    a = np.asarray(fwd(w, jnp.asarray(toks), pos))
    toks[30:] = 0
    b = np.asarray(fwd(w, jnp.asarray(toks), pos))
    np.testing.assert_array_equal(a, b)
