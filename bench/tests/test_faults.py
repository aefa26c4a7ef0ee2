"""A whole run at test size on the CPU, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` has to come out false
for each fault the cell can have, and true with nothing broken."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.conftest import harness


def _wrap_tick(eng, change):
    tick = eng._tick_fn

    def broken(params, cache, *rest):
        before = jax.tree_util.tree_map(jnp.copy, cache)   # cache is donated
        return change(eng, before, tick(params, cache, *rest))
    eng._tick_fn = broken


def state_unchanged(eng):
    """The tick hands back the cache it was given: no key or value of the
    new token is kept and no length advances."""
    _wrap_tick(eng, lambda e, before, out: (before,) + tuple(out[1:]))


def token_altered(eng):
    """Every 16th tick, the token each slot produces is replaced by the
    next id."""
    def change(e, before, out):
        if e.decode_calls % 16 == 15:
            out = (out[0], (out[1] + 1) % e.cfg.vocab_size) + tuple(out[2:])
        return out
    _wrap_tick(eng, change)


def half_batch_left_out(eng):
    """Each tick computes half of the slots, the two halves in turn: the
    tokens of the half left out come back as id 0."""
    def change(e, before, out):
        half = out[1].shape[0] // 2
        lo = (e.decode_calls % 2) * half
        return (out[0], out[1].at[lo:lo + half].set(0)) + tuple(out[2:])
    _wrap_tick(eng, change)


def first_token_altered(eng):
    """Admission prefill's first token of each request is replaced by the
    next id: its logits come out shifted by one along the vocabulary."""
    prefill = eng._prefill_fn

    def broken(*args):
        logits0, src = prefill(*args)
        return jnp.roll(logits0, 1, axis=-1), src
    eng._prefill_fn = broken


def compiles_in_window(eng):
    """A program that first compiles inside the window."""
    def change(e, before, out):
        if e.decode_calls == 40:
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 5)))
        return out
    _wrap_tick(eng, change)


def falls_back(eng):
    """The engine records a fallback to its reference graphs."""
    def change(e, before, out):
        if e.decode_calls == 40:
            e.fallback_events.append((e.decode_calls, "kernel->fallback"))
        return out
    _wrap_tick(eng, change)


def test_sound_run_is_correct(capsys):
    rc, res = harness(capsys, seed=2**31 + 99)
    assert rc == 0 and res["correct"], res
    assert list(res)[-1] == "checks"
    assert res["checks"]["served_gap"]["value"] <= \
        res["checks"]["served_gap"]["limit"]


@pytest.mark.parametrize("fault,check", [
    (state_unchanged, "served_gap"), (token_altered, "served_gap"),
    (half_batch_left_out, "served_gap"), (first_token_altered, "served_gap"),
    (compiles_in_window, "window_compiles"), (falls_back, "fallbacks")])
def test_fault_is_not_correct(capsys, fault, check):
    rc, res = harness(capsys, seed=4242, engine_hook=fault)
    assert rc == 0 and res is not None
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]
