"""A run with the timed path broken underneath comes out not correct, once
for each fault a served cell can have. (The cells have no exchange between
chips: each replica holds a whole model on its own device.)"""
import jax
import jax.numpy as jnp
import pytest

import _bench_tiny as tiny
from repro.runtime import serve_engine


def token_altered(monkeypatch):
    """Each decoded token is the next vocabulary id after the argmax."""
    sound = serve_engine.Replica.decode
    monkeypatch.setattr(serve_engine.Replica, "decode",
                        lambda self, t, p: jnp.roll(sound(self, t, p), 1, axis=-1))


def first_token_altered(monkeypatch):
    """The token admission takes from the prefill is shifted."""
    sound = serve_engine.Replica.prefill
    monkeypatch.setattr(serve_engine.Replica, "prefill",
                        lambda self, s, t: jnp.roll(sound(self, s, t), 1, axis=-1))


def state_unchanged(monkeypatch):
    """Decode returns its logits but leaves the cache as it was: a copy
    taken before the call, since a decode that donates its cache deletes
    the buffers it was given."""
    sound = serve_engine.Replica.decode

    def decode(self, tokens, positions):
        before = jax.tree.map(jnp.copy, self.cache)
        out = sound(self, tokens, positions)
        self.cache = before
        return out
    monkeypatch.setattr(serve_engine.Replica, "decode", decode)


def half_batch_left_out(monkeypatch):
    """The second half of the slots get the first half's logits."""
    sound = serve_engine.Replica.decode

    def decode(self, tokens, positions):
        out = sound(self, tokens, positions)
        half = out.shape[0] // 2
        return out.at[half:].set(out[:half])
    monkeypatch.setattr(serve_engine.Replica, "decode", decode)


def state_unchanged_donated(monkeypatch):
    """``state_unchanged`` under a decode that donates its cache, as the
    served decode may: the fault still comes out not correct, not as a
    read of a deleted buffer."""
    sound_init = serve_engine.Replica.__init__

    def init(self, *args, **kwargs):
        sound_init(self, *args, **kwargs)
        self._decode = jax.jit(self.model.decode, donate_argnums=(1,))
    monkeypatch.setattr(serve_engine.Replica, "__init__", init)
    state_unchanged(monkeypatch)


@pytest.mark.parametrize("fault", [token_altered, first_token_altered,
                                   state_unchanged, state_unchanged_donated,
                                   half_batch_left_out])
def test_fault_makes_the_run_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    # The closed loop keeps every slot busy, so each fault reaches the
    # sampled requests.
    result = tiny.run(seed=2**33 + 7, mix=tiny.tiny_mix("azure-conv-closed"))
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > result["checks"]["logit_gap"]["limit"]
