"""Decoder-LM assembly: embeddings → N blocks (mixer + FFN) → head.

One code path drives all ten assigned architectures via ``ModelConfig``:
mixer per layer ∈ {attn, mamba, rwkv}, FFN per layer ∈ {dense, moe,
rwkv_cmix}. Layers are **python-unrolled** (a deliberate dry-run requirement:
`compiled.cost_analysis()` counts `while` bodies once, so production configs
avoid `lax.scan` over layers; see DESIGN.md).

Serving-time CAMP integration: :func:`quantize_params` converts every GEMM
weight to a :class:`QuantizedTensor`; the same forward then routes through
the quantized pipeline.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.modules import (column_linear, gated_mlp, rms_norm,
                                  softmax_xent)
from repro.parallel.sharding import logical

MOE_AUX_COEF = 0.01


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, cfg.n_layers + 3)
    params = init_top(keys, cfg)
    params["layers"] = [
        init_layer(keys[i + 2], cfg, cfg.mixer_of(i), cfg.ffn_of(i))
        for i in range(cfg.n_layers)]
    return params


def init_top(keys, cfg: ModelConfig) -> dict:
    """Embedding, final norm and (untied) head from ``init_params``' keys."""
    dt = _dtype(cfg)
    params: dict = {
        "embedding": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size)) * 0.02).astype(dt)
    return params


def init_layer(key, cfg: ModelConfig, mixer: str, ffn: str) -> dict:
    """One block's parameters (``init_params`` passes layer i its key)."""
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    layer: dict = {"ln1": jnp.ones((cfg.d_model,), dt),
                   "ln2": jnp.ones((cfg.d_model,), dt)}
    if mixer == "attn":
        layer["attn"] = attn_mod.init_attention(k1, cfg, dt)
    elif mixer == "mamba":
        layer["mamba"] = ssm_mod.init_mamba(k1, cfg, dt)
    elif mixer == "rwkv":
        layer["rwkv_tm"] = rwkv_mod.init_rwkv_time_mix(k1, cfg, dt)
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        k3 = jax.random.split(k2, 3)
        d, f = cfg.d_model, cfg.d_ff
        layer["mlp"] = {
            "w_gate": (jax.random.normal(k3[0], (d, f)) * d ** -0.5).astype(dt),
            "w_up": (jax.random.normal(k3[1], (d, f)) * d ** -0.5).astype(dt),
            "w_down": (jax.random.normal(k3[2], (f, d)) * f ** -0.5).astype(dt),
        }
    elif ffn == "moe":
        layer["moe"] = moe_mod.init_moe(k2, cfg, dt)
    elif ffn == "rwkv_cmix":
        layer["rwkv_cm"] = rwkv_mod.init_rwkv_channel_mix(k2, cfg, dt)
    return layer


def _block(lp: dict, cfg: ModelConfig, i: int, h: jax.Array,
           positions: jax.Array, cache: Optional[dict], cache_pos,
           qmode: str):
    """One residual block. Returns (h, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
    mixer = cfg.mixer_of(i)
    if mixer == "attn":
        y, c_new = attn_mod.attention(
            lp["attn"], cfg, hn, positions,
            cache=None if cache is None else cache.get("attn"),
            cache_pos=cache_pos, qmode=qmode)
        c_out = None if c_new is None else {"attn": c_new}
    elif mixer == "mamba":
        y, c_new = ssm_mod.mamba_mixer(
            lp["mamba"], cfg, hn,
            cache=None if cache is None else cache.get("mamba"), qmode=qmode)
        c_out = None if c_new is None else {"mamba": c_new}
    else:  # rwkv
        y, c_new = rwkv_mod.rwkv_time_mix(
            lp["rwkv_tm"], cfg, hn,
            cache=None if cache is None else cache.get("rwkv_tm"), qmode=qmode)
        c_out = None if c_new is None else {"rwkv_tm": c_new}
    h = h + y
    h = logical(h, "batch", "seq_act", "embed")

    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    ffn = cfg.ffn_of(i)
    if ffn == "dense":
        y = gated_mlp(hn, lp["mlp"], qmode=qmode)
    elif ffn == "moe":
        y, aux = moe_mod.moe_ffn(lp["moe"], cfg, hn, qmode=qmode)
    else:  # rwkv channel mix
        y, c_cm = rwkv_mod.rwkv_channel_mix(
            lp["rwkv_cm"], cfg, hn,
            cache=None if cache is None else cache.get("rwkv_cm"), qmode=qmode)
        if c_cm is not None:
            c_out = {**(c_out or {}), "rwkv_cm": c_cm}
    h = h + y
    h = logical(h, "batch", "seq_act", "embed")
    return h, c_out, aux


def forward(params: dict, cfg: ModelConfig, inputs: jax.Array,
            positions: Optional[jax.Array] = None, *,
            caches: Optional[list] = None, cache_pos=None,
            qmode: Optional[str] = None, last_logits_only: bool = False,
            return_hidden: bool = False):
    """inputs: int tokens (B,S) or float embeddings (B,S,D) when
    ``cfg.embedding_inputs``. Returns (logits, new_caches, aux).

    ``last_logits_only``: compute the head for the final position only
    (prefill never needs the other 32k×V logits).
    ``return_hidden``: return the final hidden states instead of logits
    (the training loss streams the head via ``chunked_xent``).
    """
    qmode = cfg.qmode if qmode is None else qmode
    b, s = inputs.shape[:2]
    if positions is None:
        base = jnp.arange(s)[None] if cache_pos is None else cache_pos + jnp.arange(s)[None]
        positions = jnp.broadcast_to(base, (b, s))

    if jnp.issubdtype(inputs.dtype, jnp.integer):
        h = params["embedding"][inputs].astype(_dtype(cfg))
    else:
        assert cfg.embedding_inputs, "float inputs need embedding_inputs cfg"
        h = inputs.astype(_dtype(cfg))
    h = logical(h, "batch", "seq_act", "embed")

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for i, lp in enumerate(params["layers"]):
        cache_i = caches[i] if caches is not None else None
        if cfg.remat and caches is None:
            blk = jax.checkpoint(
                lambda lp_, h_, i_=i: _block(lp_, cfg, i_, h_, positions,
                                             None, cache_pos, qmode))
            h, _, aux = blk(lp, h)
        else:
            h, c_out, aux = _block(lp, cfg, i, h, positions, cache_i,
                                   cache_pos, qmode)
            if new_caches is not None:
                new_caches.append(c_out)
        aux_total = aux_total + aux

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return h, new_caches, aux_total
    if last_logits_only:
        h = h[:, -1:]
    head = params["embedding"].T if cfg.tie_embeddings else params["lm_head"]
    logits = column_linear(h, head,
                           qmode="none" if cfg.tie_embeddings else qmode)
    # batch_out drops the model axis from the batch so 'vocab' can carry it:
    # vocab-sharded logits keep the (B,S,V) xent buffers and the lm_head/
    # embedding f32 gradients sharded (biggest single-param grad in training).
    logits = logical(logits, "batch_out", None, "vocab")
    return logits, new_caches, aux_total


def loss_fn(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch = {'inputs': (B,S) int or (B,S,D) float, 'labels': (B,S) int}.

    Streams the vocabulary head (chunked_xent) — full (B,S,V) logits are
    never materialized.
    """
    from repro.models.modules import chunked_xent
    h, _, aux = forward(params, cfg, batch["inputs"], return_hidden=True)
    head = params["embedding"].T if cfg.tie_embeddings else params["lm_head"]
    loss = chunked_xent(h, head, batch["labels"])
    if cfg.moe_experts:
        loss = loss + MOE_AUX_COEF * aux
    return loss


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                kv_dtype: Optional[str] = None) -> list:
    """Per-layer decode caches; ``kv_dtype='int8'`` quantizes attention KV
    (per-page dynamic scales — see :mod:`repro.serving.kv_cache`)."""
    dt = _dtype(cfg)
    caches = []
    for i in range(cfg.n_layers):
        mixer = cfg.mixer_of(i)
        c: dict = {}
        if mixer == "attn":
            c["attn"] = attn_mod.init_cache(cfg, batch, max_len, dt,
                                            kv_dtype=kv_dtype)
        elif mixer == "mamba":
            c["mamba"] = ssm_mod.init_mamba_cache(cfg, batch, dt)
        else:
            h = cfg.d_model // cfg.rwkv_head_dim
            c["rwkv_tm"] = {
                "s": jnp.zeros((batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                               jnp.float32),
                "x_prev": jnp.zeros((batch, cfg.d_model), dt),
            }
        if cfg.ffn_of(i) == "rwkv_cmix":
            c["rwkv_cm"] = {"x_prev": jnp.zeros((batch, cfg.d_model), dt)}
        caches.append(c)
    return caches


# ---------------------------------------------------------------------------
# PTQ: CAMP-quantize every GEMM weight in a params tree
# ---------------------------------------------------------------------------
_QUANT_KEYS = {"wq", "wk", "wv", "wo", "wr", "wg", "w_gate", "w_up", "w_down",
               "in_proj", "out_proj", "x_proj", "lm_head"}
_MIN_K = 64   # skip tiny projections (LoRA/dt) — not worth integer path


def quantize_params(params: dict, cfg: ModelConfig, qmode: str) -> dict:
    """Post-training quantization pass: weights → QuantizedTensor (CAMP)."""
    from repro.core.camp import prepare_weight, weight_bits
    from repro.models.moe import quantize_expert_weight
    if qmode == "none":
        return params
    bits = weight_bits(qmode)

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path + (i,)) for i, v in enumerate(tree)]
        key = path[-1] if path else ""
        if (key in _QUANT_KEYS and hasattr(tree, "ndim")):
            if tree.ndim == 2 and tree.shape[0] >= _MIN_K and tree.shape[0] % 2 == 0:
                if "experts" in path:
                    return quantize_expert_weight(tree[None], bits)  # defensive
                return prepare_weight(tree, qmode)
            if tree.ndim == 3 and "experts" in path and tree.shape[1] % 2 == 0:
                return quantize_expert_weight(tree, bits)
        return tree

    return walk(params)
