"""`correct` comes out false for the control and for broken timed paths.

At smoke widths on the CPU: the control (the reference at float8 in
the program's place) reads a widest logit gap far above the program's,
and above the limit the program stays under; and a full run of the
harness, with the serve path broken underneath in each way a serving
cell can be, reports `correct: false`.

Smoke readings (CPU, 6 seeds, 128 served tokens each): the program's
widest gap 0.012-0.037, the control's 0.41-0.68. The limit here sits
between them.
"""

import jax.numpy as jnp

from bench import control, harness
from bench.tests import smoke

SMOKE_LIMIT = 0.15


def _config():
    c = smoke.config(harness.load_cell(smoke.CELL)[1])
    c["check"]["logit_gap_limit"] = SMOKE_LIMIT
    return c


def test_control_fails_where_the_program_passes():
    base = harness.load_cell(smoke.CELL)
    s = harness.Session(smoke.CELL, 31, config=_config(),
                        traffic=smoke.closed_traffic(base[2], n=8),
                        peaks=smoke.PEAKS)
    for r in control.readings(s, [31, 32, 33], 60.0, log=lambda m: None):
        assert r["tokens"] >= 100
        assert r["logit_gap"] <= SMOKE_LIMIT < r["control_gap"]
        assert r["control_gap"] >= 3 * r["logit_gap"]


def test_sound_run_is_correct():
    r = smoke.run(config=_config())
    assert r["correct"] and r["failed"] == 0
    assert r["checks"]["logit_gap"]["value"] <= SMOKE_LIMIT


def test_token_altered_where_produced(monkeypatch):
    """The sampler hands out the token after the argmax."""
    from repro.serving import engine, sampling

    def make_sampler(cfg):
        inner = sampling.make_sampler(cfg)

        def shifted(logits, keys):
            return ((inner(logits, keys) + 1) % logits.shape[-1]).astype(
                jnp.int32)
        return shifted

    monkeypatch.setattr(engine, "make_sampler", make_sampler)
    r = smoke.run(config=_config())
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_step_returns_its_state_unchanged(monkeypatch):
    """The decode step drops its update of the cache (no K/V written,
    no length advanced)."""
    from repro.models import transformer
    monkeypatch.setattr(transformer, "_update_cache_after_step",
                        lambda cache, *args, **kwargs: cache)
    r = smoke.run(config=_config())
    assert not r["correct"]
