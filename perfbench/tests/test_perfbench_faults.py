"""The run's check sees a broken timed path: with a fault planted under the
serve path, ``correct`` comes out false."""
import jax.numpy as jnp
import pytest

from perfbench.tests.rehearsal import TINY, rehearse


def _engines(fe):
    return [e for _, e in fe.pool.engines()]


def alter_tokens(fe):
    """Every decoded token is reported one id off where it is produced."""
    for eng in _engines(fe):
        step = eng._fused_step

        def bad(params, cache, state, step=step):
            nxt, bits, cache, state = step(params, cache, state)
            return (nxt + 1) % TINY["vocab_size"], bits, cache, state
        eng._fused_step = bad


def state_unchanged(fe):
    """The decode step hands back the state it was given: positions,
    last tokens and draw counters never advance."""
    for eng in _engines(fe):
        step = eng._fused_step

        def bad(params, cache, state, step=step):
            kept = {k: jnp.copy(v) for k, v in state.items()}
            nxt, bits, cache, _ = step(params, cache, state)
            return nxt, bits, cache, kept
        eng._fused_step = bad


@pytest.mark.parametrize("fault", [alter_tokens, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    out = rehearse(monkeypatch, hook=fault)
    assert out["correct"] is False
    gap = out["compared"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_sound_run_is_correct(monkeypatch):
    out = rehearse(monkeypatch)
    assert out["correct"] is True
