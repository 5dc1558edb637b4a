"""GQA attention with rope / qk-norm / qkv-bias, KV cache, q-chunked prefill.

Grouped computation never materializes repeated KV heads: q is viewed as
(B, S, KV, H/KV, hd) and contracted against (B, T, KV, hd) directly.

Causal prefill at 32k uses **q-chunking** (python-unrolled, so the multi-pod
dry-run's cost analysis sees every FLOP): each (B, chunk, ...) q-slice attends
to the full KV — exact, no online-softmax state, peak memory ∝ chunk × T
instead of T × T.

KV caching goes through :mod:`repro.serving.kv_cache`:

* :class:`~repro.serving.kv_cache.DenseKVCache` — the (B, max_len) slab
  (training/prefill and the legacy batched decode path). int8 slabs carry
  per-page dynamic scales; all conversion lives in the cache module.
* :class:`~repro.serving.kv_cache.PagedDecodeCache` — a page-pool view used
  by the continuous-batching engine: append goes to block-table pages and
  attention runs the paged int8 decode kernel
  (:mod:`repro.kernels.paged_attention`), so the quantized cache is never
  materialized as f32 in HBM.

Tensor-parallel serving: under an active ``mode='serve'`` mesh context
(:func:`repro.parallel.sharding.serve_tp`) with a kv-head count divisible by
the model axis, the paged branches run the **head-sharded shard_map kernel
wrappers** (each device attends over its local heads of its local page
shards; zero KV bytes on the wire) and the output projection runs the
explicit row-parallel path — one (optionally int8-compressed) all-reduce
per attention layer. Indivisible head counts (qwen2-0.5b's 14 over
model=16) degrade gracefully to the replicated single-device path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_attention, paged_attention_tp
from repro.kernels.paged_prefill import (paged_prefill_attention,
                                         paged_prefill_attention_tp)
from repro.models.config import ModelConfig
from repro.models.modules import (apply_rope, column_linear, linear,
                                  rms_norm, rope_freqs, row_parallel_linear,
                                  tp_shardable)
from repro.parallel.sharding import (effective_model_shards, logical,
                                     serve_tp)
from repro.serving.kv_cache import (DEFAULT_PAGE_SIZE, DenseKVCache,
                                    PagedDecodeCache, PagedPrefillCache)

_NEG = -1e30


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * sc).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv * hd)) * sc).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv * hd)) * sc).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * sc).astype(dtype),
    }
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((h * hd,), dtype)
        p["wk_bias"] = jnp.zeros((kv * hd,), dtype)
        p["wv_bias"] = jnp.zeros((kv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _grouped_attn(q, k, v, q_pos, k_pos, *, k_len: Optional[jax.Array] = None):
    """q: (B,S,KV,G,hd); k,v: (B,T,KV,hd); positions for causal masking.

    ``k_len``: optional valid-length (decode: cache fill level). Returns
    (B,S,KV,G,hd).
    """
    hd = q.shape[-1]
    # bf16 operands, f32 accumulation (MXU semantics) — no f32 copies of q/k
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    mask = q_pos[:, None] >= k_pos[None, :]                      # (S, T) causal
    if k_len is not None:
        mask = mask & (k_pos[None, :] < k_len)
    scores = jnp.where(mask[None, None, None], scores, _NEG)
    # f32 softmax for stability; probs stored bf16 (flash-attention practice)
    # — halves the largest attention buffer.
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.astype(q.dtype)


def attention(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              *, cache=None, cache_pos: Optional[jax.Array] = None,
              qmode: str = "none"):
    """x: (B, S, D). Returns (y, new_cache).

    * cache None                        → full causal self-attention (train).
    * DenseKVCache, S > 1               → prefill: attend + fill cache[0:S].
    * DenseKVCache, S == 1, cache_pos   → decode: append + attend over prefix.
    * PagedPrefillCache                 → chunked paged prefill: quantize the
      chunk's KV straight into block-table pages, causal flash attention
      over every cached page (no dense KV staging slab). The speculative
      engine's γ+1-token **verify panels** ride this same branch — their
      ``q_start`` resumes mid-page, which the write-once token-granular
      page format makes exact (the panel reads/writes the very bytes
      sequential decode would have).
    * PagedDecodeCache, S == 1          → ragged decode: append to block-table
      pages + paged int8 attention (per-sequence positions, no cache_pos).
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // kv

    q = column_linear(x, p["wq"], p.get("wq_bias"),
                      qmode=qmode).reshape(b, s, h, hd)
    k = column_linear(x, p["wk"], p.get("wk_bias"),
                      qmode=qmode).reshape(b, s, kv, hd)
    v = column_linear(x, p["wv"], p.get("wv_bias"),
                      qmode=qmode).reshape(b, s, kv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    cos, sin = rope_freqs(positions, hd, cfg.rope_theta)         # (B,S,hd/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = logical(q, "batch", "seq", "heads", "head_dim")
    k = logical(k, "batch", "seq", "kv_heads", "head_dim")
    v = logical(v, "batch", "seq", "kv_heads", "head_dim")

    # head-sharded TP applies when every kv shard holds whole head groups
    mesh, tp = serve_tp()
    head_tp = mesh is not None and effective_model_shards(mesh, kv) > 1

    def _out_proj(out):
        if head_tp and tp_shardable(p["wo"], tp):
            return row_parallel_linear(out, p["wo"], mesh=mesh, qmode=qmode)
        return linear(out, p["wo"], qmode=qmode)

    if isinstance(cache, PagedPrefillCache):
        assert b == 1, "paged prefill runs one sequence's chunk at a time"
        new_cache = cache.write_chunk(jnp.swapaxes(k, 1, 2),
                                      jnp.swapaxes(v, 1, 2))
        qp = jnp.transpose(q.reshape(s, kv, g, hd), (1, 0, 2, 3))
        if head_tp:
            ctx = paged_prefill_attention_tp(
                qp, new_cache.k_pages, new_cache.v_pages, new_cache.k_scale,
                new_cache.v_scale, new_cache.table, mesh=mesh,
                q_start=new_cache.q_start,
                pages_per_step=new_cache.pages_per_step)
        else:
            ctx = paged_prefill_attention(
                qp, new_cache.k_pages, new_cache.v_pages, new_cache.k_scale,
                new_cache.v_scale, new_cache.table, q_start=new_cache.q_start,
                pages_per_step=new_cache.pages_per_step)
        out = jnp.transpose(ctx, (1, 0, 2, 3)).reshape(1, s, h * hd)
        return _out_proj(out), new_cache

    if isinstance(cache, PagedDecodeCache):
        assert s == 1, "paged cache is decode-only (one token per sequence)"
        new_cache = cache.append(jnp.swapaxes(k, 1, 2)[:, :, 0],
                                 jnp.swapaxes(v, 1, 2)[:, :, 0])
        if head_tp:
            ctx = paged_attention_tp(
                q.reshape(b, kv, g, hd), new_cache.k_pages,
                new_cache.v_pages, new_cache.k_scale, new_cache.v_scale,
                new_cache.tables, new_cache.lengths, mesh=mesh)
        else:
            ctx = paged_attention(q.reshape(b, kv, g, hd), new_cache.k_pages,
                                  new_cache.v_pages, new_cache.k_scale,
                                  new_cache.v_scale, new_cache.tables,
                                  new_cache.lengths)
        return _out_proj(ctx.reshape(b, 1, h * hd)), new_cache

    new_cache = None
    if cache is None:
        k_all, v_all, k_pos, k_len = k, v, positions[0], None
    else:
        k_t = jnp.swapaxes(k, 1, 2)                              # (B,KV,S,hd)
        v_t = jnp.swapaxes(v, 1, 2)
        if s > 1:   # prefill from position 0
            new_cache = cache.write_prefill(k_t, v_t)
            k_all, v_all, k_pos, k_len = k, v, positions[0], None
        else:       # decode: append at cache_pos, attend over whole cache
            new_cache = cache.append(k_t, v_t, cache_pos)
            k_all, v_all = new_cache.read(x.dtype)               # (B,T,KV,hd)
            k_pos = jnp.arange(k_all.shape[1])
            k_len = cache_pos + 1

    qg = q.reshape(b, s, kv, g, hd)
    if cache is not None and s == 1:
        q_pos = jnp.full((1,), 0) + cache_pos
        out = _grouped_attn(qg, k_all, v_all, q_pos, k_pos, k_len=k_len)
    elif cfg.attn_q_chunk and s > cfg.attn_q_chunk:
        # exact q-chunked causal attention (python-unrolled)
        nc = s // cfg.attn_q_chunk
        assert s % cfg.attn_q_chunk == 0, (s, cfg.attn_q_chunk)
        k_pos_full = positions[0]
        chunks = []
        for i in range(nc):
            sl = slice(i * cfg.attn_q_chunk, (i + 1) * cfg.attn_q_chunk)
            chunks.append(_grouped_attn(qg[:, sl], k_all, v_all,
                                        k_pos_full[sl], k_pos_full))
        out = jnp.concatenate(chunks, axis=1)
    else:
        q_pos = positions[0]
        out = _grouped_attn(qg, k_all, v_all, q_pos, k_pos)

    out = out.reshape(b, s, h * hd)
    y = linear(out, p["wo"], qmode=qmode)
    return y, new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, *,
               kv_dtype: Optional[str] = None,
               page_size: Optional[int] = None) -> DenseKVCache:
    """Dense slab cache; ``kv_dtype='int8'`` stores KV quantized with
    per-page dynamic scales (see :mod:`repro.serving.kv_cache`)."""
    return DenseKVCache.init(
        batch, cfg.n_kv_heads, max_len, cfg.hd, dtype,
        quantized=(kv_dtype == "int8"),
        page_size=page_size or DEFAULT_PAGE_SIZE)
