"""The harness, driven without a chip at a small size, with the timed path
broken underneath: ``correct`` has to come out false.

The faults a serving cell can have: a decode step that returns its state
unchanged (the new key and value rows are never written), and a token
altered where it is produced. The stale-state fault runs on the untied
configuration: at this size a tied head's logits are led by the input
token's own embedding, which hides a missing cache row.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.fixture
def session(monkeypatch):
    from repro.runtime.session import QuantizedSession

    return monkeypatch, QuantizedSession


@pytest.mark.parametrize("tied", [True, False])
def test_sound_run_is_correct(tied):
    out = tiny.run(seconds=2.0, tied=tied)
    assert out["correct"], out["checks"]
    assert out["checks"]["gap_max"]["value"] <= tiny.LIMIT
    assert out["checks"]["dev_ms"]["value"] <= tiny.DEV_LIMIT


def test_state_left_unchanged_fails(session):
    mp, qs = session
    orig = qs.decode

    def stale(self, params, tok, pos, states):
        logits, _ = orig(self, params, tok, pos, states)
        return logits, states

    mp.setattr(qs, "decode", stale)
    out = tiny.run(seconds=2.0, tied=False)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("tied", [True, False])
def test_token_altered_fails(session, tied):
    import jax.numpy as jnp

    mp, qs = session
    orig = qs.decode

    def altered(self, params, tok, pos, states):
        logits, new = orig(self, params, tok, pos, states)
        return jnp.roll(logits, 1, axis=-1), new

    mp.setattr(qs, "decode", altered)
    out = tiny.run(seconds=2.0, tied=tied)
    assert not out["correct"], out["checks"]
