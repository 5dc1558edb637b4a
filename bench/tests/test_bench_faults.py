"""A run with the timed path broken underneath comes out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from repro.serving import engine as engine_mod
from repro.serving import kv_cache


def _altered_tokens(orig):
    def sample(self, logits, reqs):
        return (orig(self, logits, reqs) + 1) % self.cfg.vocab_size
    return sample


def _half_batch(orig):
    def sample(self, logits, reqs):
        # the second half of the batch takes the first half's answers
        toks = orig(self, logits, reqs)
        half = (len(reqs) + 1) // 2
        return jnp.concatenate([toks[:half], toks[:len(reqs) - half]])
    return sample


def _no_writes(orig):
    def writeback(self, layer, cache):
        return None                   # the pool keeps its old pages
    return writeback


@pytest.mark.parametrize("target,attr,fault", [
    (engine_mod.ContinuousBatchingEngine, "_sample_tokens", _altered_tokens),
    (engine_mod.ContinuousBatchingEngine, "_sample_tokens", _half_batch),
    (kv_cache.PagePool, "writeback", _no_writes),
], ids=["token_altered", "half_batch_answered_twice", "state_unchanged"])
def test_fault_is_not_correct(tmp_path, monkeypatch, target, attr, fault):
    monkeypatch.setattr(target, attr, fault(getattr(target, attr)))
    out = tiny.run(str(tmp_path))
    assert out["correct"] is False, out["checked"]
    assert np.isfinite(out["checked"]["max_logit_gap"]["value"])
