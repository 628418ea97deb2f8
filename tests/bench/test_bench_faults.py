"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (``bench.run.run_cell``, with the
look for a chip skipped) on the small CPU cell, with one fault planted
where the program produces its output.  A served cell can have three:
a token altered where it is produced, a decode step that returns its
state (the cache) unchanged, and a scheduler that lets a LO
instruction run while a HI request waits.
"""
import jax.numpy as jnp
import pytest

import benchkit
from bench import drive


@pytest.fixture
def root(tmp_path, monkeypatch):
    benchkit.use_test_arch(monkeypatch)
    return benchkit.tiny_root(tmp_path)


def _break_decode(monkeypatch, wrap):
    build = drive.build_server

    def broken(*a, **k):
        srv = build(*a, **k)
        srv._decode = wrap(srv._decode)
        return srv
    monkeypatch.setattr(drive, "build_server", broken)


def test_sound_run_is_correct(root):
    result, checks = benchkit.run_tiny(root, seed=5)
    assert result["correct"], result["checks"]
    assert checks["max_logit_gap"]["tokens"] > 20


def test_altered_token_is_caught(root, monkeypatch):
    def wrap(decode):
        def step(p, t, c):
            logits, cache = decode(p, t, c)
            return jnp.roll(logits, 1, axis=-1), cache    # argmax + 1
        return step
    _break_decode(monkeypatch, wrap)
    result, checks = benchkit.run_tiny(root, seed=5)
    assert not result["correct"]
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]


def test_state_left_unchanged_is_caught(root, monkeypatch):
    def wrap(decode):
        return lambda p, t, c: (decode(p, t, c)[0], c)
    _break_decode(monkeypatch, wrap)
    result, checks = benchkit.run_tiny(root, seed=5)
    assert not result["correct"]
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]


def test_lo_before_waiting_hi_is_caught(root, monkeypatch):
    from repro.core.scheduler import Policy
    monkeypatch.setattr(drive.Policy, "mesc",
                        staticmethod(Policy.non_preemptive))
    result, checks = benchkit.run_tiny(root, seed=5)
    assert not result["correct"]
    assert checks["lo_while_hi"]["value"] > 0
