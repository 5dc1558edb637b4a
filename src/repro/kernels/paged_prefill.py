"""Chunked paged prefill attention: causal flash over int8 KV pages.

This is the kernel that lets prefill run **directly out of the page pool** —
no dense (B, S, hd) KV staging slab ever exists. A chunk of C new tokens
(positions [q_start, q_start + C)) attends over every page the sequence has
cached so far, including the pages the chunk itself just wrote:

* pages are gathered through the sequence's **block table** with
  scalar-prefetch BlockSpec index maps, ``pages_per_step`` pages per grid
  step — long contexts advance ``pages_per_step × page_size`` tokens per
  step instead of one page per step, amortizing grid-step issue overhead;
* int8 pages are dequantized **in-register** against their per-token scales
  (the quantized cache is never f32 in HBM);
* softmax runs online per q-chunk: running (m, l, acc) scratch in VMEM, one
  output store — the (C, T) score matrix never exists in HBM;
* causality needs masking only against token positions: the chunk sits at
  the *end* of the cached range, so there are no fully-masked kv blocks to
  skip (every page up to ``q_start + C`` is at least partially visible).

Layout: q (KV, C, G, hd) — one sequence, GQA groups folded per kv head.
Pages (P, KV, page_size, hd); scales (P, KV, page_size) — one scale per
(page, head, token) row (write-once pages); table (max_pages,) int32.
Grid (KV, ceil(n_pages / pages_per_step)), kv-steps innermost ('arbitrary').

Besides prefill, this is the **speculative-decoding verify** path: a
γ+1-token panel (last sampled token + γ draft tokens) is exactly a chunk
whose ``q_start`` is wherever decode left off — usually mid-page, which the
per-token scales make safe to resume.

``impl='auto'`` follows the repo convention: Pallas on TPU, the XLA
reference elsewhere (:func:`repro.kernels.backend.resolve_paged`). The
Pallas path requires int8 pages with scales: float pages (the bf16 paged
pool) run the reference under 'auto', and an explicit ``impl='pallas'`` over
them raises.

Tensor parallelism: :func:`paged_prefill_attention_tp` shard_maps the kernel
over a mesh's ``model`` axis by kv head (q's leading dim, the pages' head
dim) — each device writes and attends only its head shard of the sequence's
pages; the block table is replicated control state and the KV hot path is
collective-free.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.backend import interpret_mode, resolve_paged

_NEG = -1e30


# ---------------------------------------------------------------------------
# XLA reference (oracle for the kernel; the non-TPU serving path)
# ---------------------------------------------------------------------------
def paged_prefill_reference(q, k_pages, v_pages, k_scale, v_scale, table, *,
                            q_start: int, sm_scale: Optional[float] = None):
    """Gather → dequantize → causally-masked softmax, one jnp expression.

    q: (KV, C, G, hd); pages (P, KV, ps, hd); scales (P, KV, ps) per-token
    or None; table (max_pages,) int32; ``q_start`` static. Returns
    (KV, C, G, hd).
    """
    kv, c, g, hd = q.shape
    ps = k_pages.shape[2]
    kv_len = q_start + c
    n_pages = -(-kv_len // ps)
    slots = table[:n_pages]
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    def gather(pages, scales):
        x = jnp.take(pages, slots, axis=0).astype(jnp.float32)  # (np,KV,ps,hd)
        if scales is not None:
            x = x * jnp.take(scales, slots, axis=0)[..., None]
        return jnp.swapaxes(x, 0, 1).reshape(kv, n_pages * ps, hd)

    k_all = gather(k_pages, k_scale)
    v_all = gather(v_pages, v_scale)
    s = jnp.einsum("kcgh,kth->kcgt", q.astype(jnp.float32), k_all) * scale
    t_pos = jnp.arange(n_pages * ps)
    q_pos = q_start + jnp.arange(c)
    mask = t_pos[None, :] <= q_pos[:, None]                     # (C, T)
    s = jnp.where(mask[None, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("kcgt,kth->kcgh", p, v_all)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _prefill_kernel(table_ref, q_ref, *refs, pp: int, ps: int, g: int,
                    scale: float, q_start: int):
    k_refs = refs[:pp]
    v_refs = refs[pp:2 * pp]
    ks_refs = refs[2 * pp:3 * pp]
    vs_refs = refs[3 * pp:4 * pp]
    o_ref, acc_ref, m_ref, l_ref = refs[4 * pp:]
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    h = pl.program_id(0)
    q = q_ref[0].astype(jnp.float32)                            # (C*G, hd)
    # multi-page kv block: pp int8 pages stacked along tokens; their
    # per-token scales, one (1, ps) row per page, stacked along lanes
    k = jnp.concatenate([k_refs[i][0, 0].astype(jnp.float32)
                         for i in range(pp)], axis=0)           # (pp*ps, hd)
    v = jnp.concatenate([v_refs[i][0, 0].astype(jnp.float32)
                         for i in range(pp)], axis=0)
    ks = jnp.concatenate([ks_refs[i][0, pl.ds(h, 1), :]
                          for i in range(pp)], axis=1)          # (1, pp*ps)
    vs = jnp.concatenate([vs_refs[i][0, pl.ds(h, 1), :]
                          for i in range(pp)], axis=1)
    # in-register dequant: a token's scale multiplies its score column (k)
    # and its probability column (v)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (ks * scale)
    rows = q.shape[0]
    col = j * pp * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, pp * ps), 1)
    row_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, pp * ps), 0) // g
    s = jnp.where(col <= row_pos, s, _NEG)                      # causal + pad
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p * vs, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_start", "pages_per_step",
                                             "sm_scale", "interpret"))
def _paged_prefill_pallas(q, k_pages, v_pages, k_scale, v_scale, table, *,
                          q_start: int, pages_per_step: int = 1,
                          sm_scale: Optional[float] = None,
                          interpret: bool = False):
    kv, c, g, hd = q.shape
    ps = k_pages.shape[2]
    kv_len = q_start + c
    n_pages = -(-kv_len // ps)
    pp = max(1, min(pages_per_step, n_pages))
    n_steps = -(-n_pages // pp)
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    # pad the table so every (step, page-in-step) lookup is in range; slot 0
    # fetched past kv_len is masked by the position test in-kernel
    tbl = table[:n_pages]
    if n_steps * pp > n_pages:
        tbl = jnp.concatenate(
            [tbl, jnp.zeros((n_steps * pp - n_pages,), jnp.int32)])
    q2 = q.reshape(kv, c * g, hd)

    def page_map(i):
        return lambda hi, ji, t: (t[ji * pp + i], hi, 0, 0)

    # scales ride with every head of the page: a (1, 1, ps) block would
    # break Mosaic's (8, 128)-or-full rule on its last two dims
    def scale_map(i):
        return lambda hi, ji, t: (t[ji * pp + i], 0, 0)

    page_spec = [pl.BlockSpec((1, 1, ps, hd), page_map(i)) for i in range(pp)]
    scale_spec = [pl.BlockSpec((1, kv, ps), scale_map(i)) for i in range(pp)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kv, n_steps),
        in_specs=[pl.BlockSpec((1, c * g, hd), lambda hi, ji, t: (hi, 0, 0))]
        + page_spec + page_spec + scale_spec + scale_spec,
        out_specs=pl.BlockSpec((1, c * g, hd), lambda hi, ji, t: (hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((c * g, hd), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, pp=pp, ps=ps, g=g, scale=scale,
                          q_start=q_start),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kv, c * g, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(tbl, q2, *([k_pages] * pp), *([v_pages] * pp),
      *([k_scale] * pp), *([v_scale] * pp))
    return out.reshape(kv, c, g, hd)


def paged_prefill_attention(q, k_pages, v_pages, k_scale, v_scale, table, *,
                            q_start: int, pages_per_step: int = 1,
                            sm_scale: Optional[float] = None,
                            impl: str = "auto",
                            interpret: Optional[bool] = None):
    """Chunked paged prefill attention; see :func:`paged_prefill_reference`
    for shapes. ``q_start`` / ``pages_per_step`` must be static."""
    if resolve_paged(impl, k_scale is not None) == "pallas":
        return _paged_prefill_pallas(
            q, k_pages, v_pages, k_scale, v_scale, table,
            q_start=q_start, pages_per_step=pages_per_step, sm_scale=sm_scale,
            interpret=interpret_mode(interpret))
    return paged_prefill_reference(q, k_pages, v_pages, k_scale, v_scale,
                                   table, q_start=q_start, sm_scale=sm_scale)


def paged_prefill_attention_tp(q, k_pages, v_pages, k_scale, v_scale, table,
                               *, mesh, axis: str = "model", q_start: int,
                               pages_per_step: int = 1,
                               sm_scale: Optional[float] = None,
                               impl: str = "auto",
                               interpret: Optional[bool] = None):
    """Head-sharded tensor-parallel chunked paged prefill.

    Same shapes as :func:`paged_prefill_reference`; q's kv dim (dim 0) and
    the pages' head dim must divide ``mesh.shape[axis]``. Each device runs
    the chunk's causal flash attention over its local heads of its local
    page shards; the block table is replicated and no KV byte crosses the
    interconnect.
    """
    kv = q.shape[0]
    if kv % mesh.shape[axis]:
        raise ValueError(
            f"kv heads {kv} not divisible by {axis}={mesh.shape[axis]}")
    fn = _paged_prefill_tp_fn(mesh, axis, q_start, pages_per_step, sm_scale,
                              impl, interpret, k_scale is not None)
    return fn(q, k_pages, v_pages, k_scale, v_scale, table)


@functools.lru_cache(maxsize=None)
def _paged_prefill_tp_fn(mesh, axis, q_start, pages_per_step, sm_scale, impl,
                         interpret, quantized):
    """One jitted shard_map program per configuration (a fresh closure per
    call would recompile on every eager prefill chunk)."""
    qspec = P(axis, None, None, None)
    head4 = P(None, axis, None, None)
    sspec = P(None, axis, None) if quantized else None

    def body(q_, kp, vp, ks, vs, tb):
        return paged_prefill_attention(
            q_, kp, vp, ks, vs, tb, q_start=q_start,
            pages_per_step=pages_per_step, sm_scale=sm_scale, impl=impl,
            interpret=interpret)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, head4, head4, sspec, sspec, P(None)),
        out_specs=qspec, check_vma=False))
