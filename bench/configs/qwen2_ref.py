"""Plain float32 reference of a Qwen2 decoder, for the benchmark's check.

Written from the published architecture (arXiv:2407.10671; Qwen2's
``modeling_qwen2``): token embedding; per block RMSNorm, q/k/v projections
with bias, rotary embedding on the half-split head dims, causal grouped-query
attention, output projection, residual, RMSNorm, SiLU-gated MLP, residual;
final RMSNorm and the vocabulary head (tied to the embedding or not).

It imports nothing of the program under test. Its weights are drawn here
from the run seed by the same recipe the serving stack uses
(``jax.random`` keys split per block, normal draws scaled by fan-in, bf16),
then quantized as the configuration states: symmetric per-output-channel
integers (int8 in [-127, 127], int4 in [-7, 7], scale = column absmax /
qmax) and dequantized to float32. Activations, attention and the cache are
float32 throughout; matmuls run at ``highest`` precision.

It runs one block at a time over every sampled sequence, so only one
block's float32 weights live on the device at once.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QMAX = {8: 127.0, 4: 7.0}
PAD = 512


def model_shape(cfg: dict) -> dict:
    """The model's sizes as run, from the configuration file."""
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return {
        "n_layers": int(cfg["num_hidden_layers"]),
        "d_model": d,
        "n_heads": heads,
        "n_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim", d // heads)),
        "d_ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def _quantized(w, bits):
    """Per-output-channel symmetric quantization, dequantized to f32."""
    if bits is None:
        return w.astype(jnp.float32)
    w = w.astype(jnp.float32)
    qmax = QMAX[bits]
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / qmax)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _normal(key, shape, std):
    return (jax.random.normal(key, shape) * std).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "bits"))
def _top(keys, shape, bits):
    d, v = shape["d_model"], shape["vocab"]
    emb = _normal(keys[0], (v, d), 0.02)
    out = {"embedding": emb.astype(jnp.float32)}
    if shape["tied"]:
        out["head"] = out["embedding"].T
    else:
        out["head"] = _quantized(_normal(keys[1], (d, v), 0.02), bits)
    return out


@functools.partial(jax.jit, static_argnames=("shape", "bits"))
def _block_weights(key, shape, bits):
    d, h, kv = shape["d_model"], shape["n_heads"], shape["n_kv_heads"]
    hd, f = shape["head_dim"], shape["d_ff"]
    k1, k2 = jax.random.split(key)
    ka = jax.random.split(k1, 4)
    km = jax.random.split(k2, 3)
    sc = d ** -0.5
    return {
        "wq": _quantized(_normal(ka[0], (d, h * hd), sc), bits),
        "wk": _quantized(_normal(ka[1], (d, kv * hd), sc), bits),
        "wv": _quantized(_normal(ka[2], (d, kv * hd), sc), bits),
        "wo": _quantized(_normal(ka[3], (h * hd, d), sc), bits),
        "w_gate": _quantized(_normal(km[0], (d, f), sc), bits),
        "w_up": _quantized(_normal(km[1], (d, f), sc), bits),
        "w_down": _quantized(_normal(km[2], (f, d), f ** -0.5), bits),
    }


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("shape",))
def _block(w, h, shape):
    """One block over one sequence h: (S, d) float32, positions 0..S-1."""
    s = h.shape[0]
    nh, kv, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    g = nh // kv
    pos = jnp.arange(s)
    x = _rms(h, shape["eps"])              # RMSNorm weights are ones
    # q/k/v biases are drawn as zeros by the recipe
    q = _rope((x @ w["wq"]).reshape(s, nh, hd), pos, shape["rope_theta"])
    k = _rope((x @ w["wk"]).reshape(s, kv, hd), pos, shape["rope_theta"])
    v = (x @ w["wv"]).reshape(s, kv, hd)
    q = q.reshape(s, kv, g, hd)
    sc = jnp.einsum("skgh,tkh->kgst", q, k) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    att = jnp.einsum("kgst,tkh->skgh", p, v).reshape(s, nh * hd)
    h = h + att @ w["wo"]
    x = _rms(h, shape["eps"])
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("shape",))
def _logits(top, h, shape):
    return _rms(h, shape["eps"]) @ top["head"]


def _frozen(shape: dict):
    return tuple(sorted(shape.items()))


class _Shape(dict):
    """A hashable dict, so it can be a static jit argument."""

    def __hash__(self):
        return hash(_frozen(self))


def logits_at(cfg: dict, seed_key, weight_bits, sequences: Sequence[List[int]],
              starts: Sequence[int]) -> List[np.ndarray]:
    """Reference logits of every sequence at positions ``starts[i]`` .. end.

    ``sequences[i]``: prompt followed by the served tokens; ``starts[i]``:
    the position whose logits predict the first served token. Returns one
    (n_i, vocab) float32 array per sequence. ``weight_bits``: 8, 4 or None.
    """
    shape = _Shape(model_shape(cfg))
    keys = jax.random.split(seed_key, shape["n_layers"] + 3)
    with jax.default_matmul_precision("highest"):
        top = _top(keys, shape, weight_bits)
        # padded at the end to a multiple of PAD, so that few lengths
        # compile; causal attention leaves the real positions unchanged
        hs = [top["embedding"][jnp.asarray(
            list(seq) + [0] * (-len(seq) % PAD), jnp.int32)]
            for seq in sequences]
        emb_only = {"head": top["head"]}
        del top
        for i in range(shape["n_layers"]):
            w = _block_weights(keys[i + 2], shape, weight_bits)
            hs = [_block(w, h, shape) for h in hs]
            del w
        out = []
        for h, st in zip(hs, starts):
            out.append(np.asarray(_logits(emb_only, h[st:], shape),
                                  np.float32))
    return out


def served_gaps(ref_logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position (0 where the two agree)."""
    ref = np.asarray(ref_logits, np.float32)[:len(served)]
    idx = np.asarray(served, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(idx)), idx]


def check(cfg: dict, seed_key, samples: Dict[str, list]) -> np.ndarray:
    """Gaps of every served token in ``samples`` ({"prompts", "served"})
    against the reference at the configuration's stated weight precision."""
    bits = {"w8a8": 8, "w4a8": 4, "w4a4": 4}[cfg["serving"]["weights"]]
    seqs = [list(p) + list(s[:-1]) for p, s in
            zip(samples["prompts"], samples["served"])]
    starts = [len(p) - 1 for p in samples["prompts"]]
    logits = logits_at(cfg, seed_key, bits, seqs, starts)
    return np.concatenate([served_gaps(lg, s) for lg, s in
                           zip(logits, samples["served"])])
