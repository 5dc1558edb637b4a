"""Paged single-token decode attention: block-table gather + in-register
int8 dequant + online softmax, all in VMEM.

Decode attention is the serving roofline's dominant term: each new token
re-reads the whole KV cache. With a paged int8 cache the HBM traffic per
step collapses to the pages a sequence actually occupies (not the
``(B, max_len)`` slab) at one byte per element — and this kernel never
materializes an f32 copy of the cache in HBM: pages are gathered via the
block table with scalar-prefetch BlockSpec index maps, dequantized
**in-register** with their per-token scales, and consumed by an online-softmax
accumulator held in VMEM scratch.

Layout: q (B, KV, G, hd) — one token per sequence, GQA groups folded per
kv head. Pages (P, KV, page_size, hd); scales (P, KV, page_size) — one
scale per (page, head, token) row, so stored bytes are write-once and
independent of how tokens were batched into the page (single appends vs
speculative verify panels). Block table (B, max_pages) int32; lengths (B,)
int32. Grid (B, KV, max_pages), pages innermost ('arbitrary') carrying
running (m, l, acc) scratch. Pages past a sequence's length are skipped
via ``pl.when`` (padded block-table slots are never touched because the
skip test uses lengths, not the table).

``impl='auto'`` follows the repo convention: Pallas on TPU, the XLA
reference elsewhere (:func:`repro.kernels.backend.resolve_paged`). The
Pallas path requires int8 pages with scales: float pages (the bf16 paged
pool) run the reference under 'auto', and an explicit ``impl='pallas'`` over
them raises.

Tensor parallelism: :func:`paged_attention_tp` shard_maps the kernel over a
mesh's ``model`` axis with every KV-head-carrying operand split by head —
each device gathers/dequantizes/attends only its local heads of its local
page shards, so the KV hot path moves **zero** bytes between devices (the
one collective of a TP decode layer is the row-parallel ``wo`` all-reduce
that follows).
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
def paged_attention_reference(q, k_pages, v_pages, k_scale, v_scale, tables,
                              lengths, *, sm_scale: Optional[float] = None):
    """Gather → dequantize → masked softmax, as one jnp expression.

    q: (B, KV, G, hd); pages (P, KV, ps, hd); scales (P, KV, ps) per-token
    or None; tables (B, max_pages) int32; lengths (B,) int32. Returns
    (B, KV, G, hd).
    """
    b, kv, g, hd = q.shape
    ps = k_pages.shape[2]
    max_pages = tables.shape[1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5

    def gather(pages, scales):
        x = jnp.take(pages, tables, axis=0)                # (B, mp, KV, ps, hd)
        x = x.astype(jnp.float32)
        if scales is not None:
            x = x * jnp.take(scales, tables, axis=0)[..., None]
        x = jnp.swapaxes(x, 1, 2)                          # (B, KV, mp, ps, hd)
        return x.reshape(b, kv, max_pages * ps, hd)

    k_all = gather(k_pages, k_scale)
    v_all = gather(v_pages, v_scale)
    s = jnp.einsum("bkgh,bkth->bkgt", q.astype(jnp.float32), k_all) * scale
    t = max_pages * ps
    mask = jnp.arange(t)[None, :] < lengths[:, None]       # (B, T)
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bkth->bkgh", p, v_all)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, ps: int, g: int,
                  scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lens_ref[b]
    h = pl.program_id(1)

    @pl.when(j * ps < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                        # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        # in-register dequant: a token's scale multiplies its score column
        # (k) and its probability column (v), so the (1, ps) scale row
        # never needs a lane→sublane relayout
        ks = ks_ref[0, pl.ds(h, 1), :]                             # (1, ps)
        vs = vs_ref[0, pl.ds(h, 1), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (ks * scale)
        col = j * ps + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        s = jnp.where(col < length, s, _NEG)
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
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_attention_pallas(q, k_pages, v_pages, k_scale, v_scale, tables,
                            lengths, *, sm_scale: Optional[float] = None,
                            interpret: bool = False):
    b, kv, g, hd = q.shape
    ps = k_pages.shape[2]
    max_pages = tables.shape[1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    grid = (b, kv, max_pages)

    def page_map(bi, hi, ji, tables_ref, lens_ref):
        return (tables_ref[bi, ji], hi, 0, 0)

    # scales ride with every head of the page: a (1, 1, ps) block would
    # break Mosaic's (8, 128)-or-full rule on its last two dims
    def scale_map(bi, hi, ji, tables_ref, lens_ref):
        return (tables_ref[bi, ji], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, hi, ji, t, le: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), page_map),
            pl.BlockSpec((1, 1, ps, hd), page_map),
            pl.BlockSpec((1, kv, ps), scale_map),
            pl.BlockSpec((1, kv, ps), scale_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, ji, t, le: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, g=g, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(tables, lengths, q, k_pages, v_pages, k_scale, v_scale)


def paged_attention(q, k_pages, v_pages, k_scale, v_scale, tables, lengths,
                    *, sm_scale: Optional[float] = None, impl: str = "auto",
                    interpret: Optional[bool] = None):
    """Paged decode attention; see :func:`paged_attention_reference` shapes."""
    if resolve_paged(impl, k_scale is not None) == "pallas":
        return _paged_attention_pallas(
            q, k_pages, v_pages, k_scale, v_scale, tables, lengths,
            sm_scale=sm_scale,
            interpret=interpret_mode(interpret))
    return paged_attention_reference(q, k_pages, v_pages, k_scale, v_scale,
                                     tables, lengths, sm_scale=sm_scale)


def paged_attention_tp(q, k_pages, v_pages, k_scale, v_scale, tables,
                       lengths, *, mesh, axis: str = "model",
                       sm_scale: Optional[float] = None, impl: str = "auto",
                       interpret: Optional[bool] = None):
    """Head-sharded tensor-parallel paged decode attention.

    Same shapes as :func:`paged_attention_reference`; the KV-head dim of
    ``q`` (dim 1) and of the pages/scales must divide ``mesh.shape[axis]``.
    Each device runs the single-device kernel over its local heads of its
    local page shards — block tables and lengths are replicated control
    state, and no KV byte crosses the interconnect.
    """
    kv = q.shape[1]
    if kv % mesh.shape[axis]:
        raise ValueError(
            f"kv heads {kv} not divisible by {axis}={mesh.shape[axis]}")
    fn = _paged_attention_tp_fn(mesh, axis, sm_scale, impl, interpret,
                                k_scale is not None)
    return fn(q, k_pages, v_pages, k_scale, v_scale, tables, lengths)


@functools.lru_cache(maxsize=None)
def _paged_attention_tp_fn(mesh, axis, sm_scale, impl, interpret,
                           quantized):
    """One jitted shard_map program per configuration (a fresh closure per
    call would recompile on every eager decode step)."""
    head4 = P(None, axis, None, None)
    sspec = P(None, axis, None) if quantized else None

    def body(q_, kp, vp, ks, vs, tb, ln):
        return paged_attention(q_, kp, vp, ks, vs, tb, ln,
                               sm_scale=sm_scale, impl=impl,
                               interpret=interpret)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(head4, head4, head4, sspec, sspec, P(None, None), P(None)),
        out_specs=head4, check_vma=False))
