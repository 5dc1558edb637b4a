"""Paged decode attention: Pallas kernel (interpret mode) vs XLA reference,
and reference vs the dense grouped-attention oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_reference)
from repro.models.attention import _grouped_attn

RNG = np.random.default_rng(0)


def _paged_int8(b, kv, ps, hd, num_pages, max_pages):
    kp = jnp.asarray(RNG.integers(-127, 128, (num_pages, kv, ps, hd)),
                     jnp.int8)
    vp = jnp.asarray(RNG.integers(-127, 128, (num_pages, kv, ps, hd)),
                     jnp.int8)
    ks = jnp.asarray(RNG.uniform(1e-3, 5e-2, (num_pages, kv, ps)),
                     jnp.float32)
    vs = jnp.asarray(RNG.uniform(1e-3, 5e-2, (num_pages, kv, ps)),
                     jnp.float32)
    tables = jnp.asarray(
        RNG.permutation(num_pages)[:b * max_pages].reshape(b, max_pages),
        jnp.int32)
    return kp, vp, ks, vs, tables


@pytest.mark.parametrize("b,kv,g,hd,ps,mp", [
    (3, 2, 3, 64, 16, 4),     # GQA
    (2, 1, 4, 32, 8, 5),      # MQA
    (1, 4, 1, 128, 16, 2),    # MHA
])
def test_kernel_matches_reference(b, kv, g, hd, ps, mp):
    kp, vp, ks, vs, tables = _paged_int8(b, kv, ps, hd, 32, mp)
    lengths = jnp.asarray(RNG.integers(1, mp * ps + 1, (b,)), jnp.int32)
    lengths = lengths.at[0].set(ps)          # exact page boundary
    q = jnp.asarray(RNG.standard_normal((b, kv, g, hd)), jnp.float32)
    ref = paged_attention_reference(q, kp, vp, ks, vs, tables, lengths)
    ker = paged_attention(q, kp, vp, ks, vs, tables, lengths,
                          impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_single_token_length():
    """length=1: only the first slot of the first page is attended."""
    b, kv, g, hd, ps, mp = 2, 2, 2, 32, 8, 3
    kp, vp, ks, vs, tables = _paged_int8(b, kv, ps, hd, 16, mp)
    lengths = jnp.ones((b,), jnp.int32)
    q = jnp.asarray(RNG.standard_normal((b, kv, g, hd)), jnp.float32)
    ref = paged_attention_reference(q, kp, vp, ks, vs, tables, lengths)
    ker = paged_attention(q, kp, vp, ks, vs, tables, lengths,
                          impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # softmax over one position ⇒ output is exactly that value row
    v0 = vp[tables[:, 0]].astype(jnp.float32) * vs[tables[:, 0]][..., None]
    np.testing.assert_allclose(np.asarray(ref),
                               np.tile(np.asarray(v0)[:, :, :1], (1, 1, g, 1)),
                               rtol=1e-5, atol=1e-5)


def test_reference_matches_dense_grouped_attn():
    """Float pages (no scales) against the model's dense attention oracle."""
    b, kv, g, hd, ps, mp = 2, 2, 2, 16, 8, 3
    t = mp * ps
    k_dense = jnp.asarray(RNG.standard_normal((b, t, kv, hd)), jnp.float32)
    v_dense = jnp.asarray(RNG.standard_normal((b, t, kv, hd)), jnp.float32)
    lengths = jnp.asarray([t - 3, ps], jnp.int32)
    # scatter the dense layout into pages row-by-row
    num_pages = b * mp
    tables = jnp.arange(num_pages, dtype=jnp.int32).reshape(b, mp)
    kp = jnp.swapaxes(k_dense.reshape(b, mp, ps, kv, hd), 2, 3).reshape(
        num_pages, kv, ps, hd)
    vp = jnp.swapaxes(v_dense.reshape(b, mp, ps, kv, hd), 2, 3).reshape(
        num_pages, kv, ps, hd)
    q = jnp.asarray(RNG.standard_normal((b, kv, g, hd)), jnp.float32)
    got = paged_attention_reference(q, kp, vp, None, None, tables, lengths)
    # dense oracle: decode-shaped _grouped_attn, whose k_len is a scalar fill
    # level — run it per sequence to emulate the ragged per-seq masking
    q5 = q.reshape(b, 1, kv, g, hd)
    outs = []
    for i in range(b):
        w = _grouped_attn(q5[i:i + 1], k_dense[i:i + 1], v_dense[i:i + 1],
                          q_pos=jnp.full((1,), t), k_pos=jnp.arange(t),
                          k_len=lengths[i])
        outs.append(w[:, 0])
    want = jnp.concatenate(outs, axis=0)               # (B, KV, G, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_explicit_pallas_over_float_pages_raises(kernel):
    """Float pages have no kernel: 'auto' runs the reference, and an
    explicit impl='pallas' is refused instead of falling back."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    kp = vp = jnp.zeros((4, 1, 8, 16), jnp.float32)
    table = jnp.zeros((2,), jnp.int32)
    if kernel == "decode":
        q = jnp.zeros((1, 1, 2, 16), jnp.float32)
        call = lambda impl: paged_attention(  # noqa: E731
            q, kp, vp, None, None, table[None], jnp.ones((1,), jnp.int32),
            impl=impl)
    else:
        q = jnp.zeros((1, 3, 2, 16), jnp.float32)
        call = lambda impl: paged_prefill_attention(  # noqa: E731
            q, kp, vp, None, None, table, q_start=0, impl=impl)
    assert call("auto").shape == q.shape
    with pytest.raises(ValueError, match="int8 pages"):
        call("pallas")
