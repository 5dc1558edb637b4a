"""A run with the timed path broken underneath comes out not correct.

The faults run on the burst mix (``tiny.BURST``), whose decode batch grows
by a row a step however fast a step is, and each case first checks that
its fault fired, so a faster or restructured engine fails with a plain
message rather than reading correct for the wrong reason."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from repro.serving import engine as engine_mod
from repro.serving import kv_cache

POOL_ARRAYS = ("k_pages", "v_pages", "k_scale", "v_scale")


@pytest.fixture(autouse=True)
def fresh_traces():
    """Programs traced earlier in the process are traced again, so a
    program jitted at module level sees the patched method; and none traced
    under a fault outlives the test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


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


def _stale_pages(orig):
    first = {}

    def writeback(self, layer, cache):
        # the pool keeps the pages of each layer's first write: a fresh copy
        # of them after every write, so it never holds an array that a call
        # which donates the pool has consumed
        if layer not in first:
            first[layer] = {k: jnp.copy(getattr(cache, k))
                            for k in POOL_ARRAYS}
        orig(self, layer, types.SimpleNamespace(
            **{k: jnp.copy(v) for k, v in first[layer].items()}))
    return writeback


def _window_rows(run):
    return [len(s.decode_lengths) for s in run.window_steps()]


@pytest.mark.parametrize("target,attr,fault,min_rows", [
    (engine_mod.ContinuousBatchingEngine, "_sample_tokens", _altered_tokens,
     1),
    (engine_mod.ContinuousBatchingEngine, "_sample_tokens", _half_batch, 2),
    (kv_cache.PagePool, "writeback", _stale_pages, 1),
], ids=["token_altered", "half_batch_answered_twice", "state_unchanged"])
def test_fault_is_not_correct(tmp_path, monkeypatch, target, attr, fault,
                              min_rows):
    calls = []
    broken = fault(getattr(target, attr))

    def counted(self, *args):
        calls.append(1)
        return broken(self, *args)
    monkeypatch.setattr(target, attr, counted)
    out, run = tiny.run(str(tmp_path), mix=tiny.BURST, full=True)
    assert calls, f"the fault did not fire: {attr} was never called"
    rows = _window_rows(run)
    assert max(rows, default=0) >= min_rows, (
        f"the fault cannot show: no window step decoded {min_rows} rows or "
        f"more (rows per window step {rows})")
    assert out["correct"] is False, out["checked"]
    gap = out["checked"]["max_logit_gap"]
    assert np.isfinite(gap["value"]) and gap["value"] > gap["limit"], gap


def test_sound_run_on_the_burst_mix_is_correct(tmp_path):
    """The control: the unbroken engine on the faults' mix reads correct,
    so the faults, not the mix, make the runs above not correct."""
    out, run = tiny.run(str(tmp_path), mix=tiny.BURST, full=True)
    rows = _window_rows(run)
    assert max(rows, default=0) >= 2, rows
    assert out["correct"] is True, out["checked"]
