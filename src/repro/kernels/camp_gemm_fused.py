"""Fused activation-quantize + CAMP GEMM Pallas TPU kernels.

The paper's CAMP pipeline quantizes the A-panel, runs the integer
outer-product accumulate, and applies the Cartesian scale — all inside one
hardware pipeline with a single store per accumulator lifetime. The seed port
broke that chain at the HBM level: ``quantize_rowwise`` ran as a separate
kernel (f32 activations read, int8 + scales written back to HBM, then re-read
by the GEMM). These kernels restore the paper's property at TPU granularity:

* the activation row-block arrives in VMEM in its storage dtype (bf16/f32),
* per-row absmax → scale → round/clip to int8 (or int4 range) happens on the
  VMEM-resident block — **the quantized activation tensor never exists in
  HBM**, and neither do its scales,
* the int32 accumulate and the scale/bias/activation epilogue run as before,
  with one store per (bm, bn) output tile.

Blocking: A uses a (bm, K) row-block whose index map is constant in (j, k),
so Pallas fetches each A row-panel from HBM exactly once per grid row and the
in-kernel K-loop slices sub-blocks out of VMEM (``pl.ds``). The per-row scale
is recomputed at k==0 of every (i, j) pass — a VPU reduction over a
VMEM-resident panel, free relative to the MXU work, and safe under Megacore
grid partitioning (no cross-j scratch dependence).

Bit-exactness: the in-kernel quantize is the same f32 expression chain as
``repro.kernels.ref.quantize_rowwise_ref``, so on block-divisible shapes the
fused w8a8 result is bit-identical to the unfused
``quantize_rowwise`` → ``camp_gemm_i8`` composition. K-padding preserves this
(zero columns don't move a row's absmax); padded M rows quantize to zeros and
are sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT4_QMAX, INT8_QMAX
from repro.kernels.camp_gemm import _epilogue_inputs
from repro.kernels.camp_gemm_w4 import _even_block_k, _unpack_k_rows
from repro.kernels.epilogue import flush_epilogue, parse_epilogue
from repro.kernels.padding import pad_2d, round_up


def _fused_kernel(*refs, stages, n_extra, bk, qmax, unpack_b):
    x_ref, b_ref, sb_ref = refs[:3]
    extra = refs[3:3 + n_extra]
    o_ref = refs[3 + n_extra]
    acc_ref, sa_ref = refs[4 + n_extra], refs[5 + n_extra]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # Per-row absmax over the whole K row — the A-panel "pack" step of the
        # paper, done on the VMEM-resident panel. Same f32 expression chain as
        # quantize_rowwise_ref, so quantized values are bit-identical.
        x32 = x_ref[...].astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
        sa_ref[...] = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_blk = x_ref[:, pl.ds(k * bk, bk)].astype(jnp.float32)
    a_q = jnp.clip(jnp.round(x_blk / sa_ref[...]), -qmax, qmax).astype(jnp.int8)
    b = b_ref[...]
    if unpack_b:
        b = _unpack_k_rows(b)  # VMEM-resident nibble unpack
    acc_ref[...] += jax.lax.dot_general(
        a_q, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        flush_epilogue(acc_ref, sa_ref, sb_ref, o_ref, stages, extra)


def _camp_gemm_fused(x, b, b_scale, *, a_bits, w_bits, block_m, block_n,
                     block_k, out_dtype, epilogue, bias, operand, interpret):
    m, k = x.shape
    if w_bits == 8:
        kb, n = b.shape
        assert k == kb, (x.shape, b.shape)
    else:
        kb, n = b.shape
        assert k == 2 * kb, (x.shape, b.shape)
    stages = parse_epilogue(epilogue)
    qmax = INT8_QMAX if a_bits == 8 else INT4_QMAX

    bm, bn = min(block_m, m), min(block_n, n)
    bk = _even_block_k(block_k, k) if w_bits == 4 else min(block_k, k)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)

    x = pad_2d(x, mp, kp)
    if w_bits == 8:
        b = pad_2d(b, kp, np_)
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    else:
        b = pad_2d(b, kp // 2, np_)
        b_spec = pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j))
    b_scale = pad_2d(b_scale, 1, np_, value=1.0)
    extra, extra_specs = _epilogue_inputs(stages, bias, operand, n=n, bm=bm,
                                          bn=bn, mp=mp, np_=np_)

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, stages=stages, n_extra=len(extra),
                          bk=bk, qmax=qmax, unpack_b=(w_bits == 4)),
        grid=grid,
        in_specs=[
            # Whole padded K row per A block: constant in (j, k) → one HBM
            # fetch per grid row, K-loop slices from VMEM.
            pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
            b_spec,
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),   # accumulator
            pltpu.VMEM((bm, 1), jnp.float32),  # per-row activation scales
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x, b, b_scale, *extra)
    return out[:m, :n]


_FUSED_STATICS = ("block_m", "block_n", "block_k", "out_dtype", "epilogue",
                  "interpret")


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def camp_gemm_fused_w8a8(
    x: jax.Array,          # (M, K) f32/bf16 activations — quantized in VMEM
    b_q: jax.Array,        # (K, N) int8
    b_scale: jax.Array,    # (1, N) f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    epilogue: str = "none",
    bias: jax.Array | None = None,
    operand: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _camp_gemm_fused(x, b_q, b_scale, a_bits=8, w_bits=8,
                            block_m=block_m, block_n=block_n, block_k=block_k,
                            out_dtype=out_dtype, epilogue=epilogue, bias=bias,
                            operand=operand, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def camp_gemm_fused_w4a8(
    x: jax.Array,          # (M, K) f32/bf16
    b_packed: jax.Array,   # (K//2, N) int8 packed int4 weights
    b_scale: jax.Array,    # (1, N) f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    epilogue: str = "none",
    bias: jax.Array | None = None,
    operand: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _camp_gemm_fused(x, b_packed, b_scale, a_bits=8, w_bits=4,
                            block_m=block_m, block_n=block_n, block_k=block_k,
                            out_dtype=out_dtype, epilogue=epilogue, bias=bias,
                            operand=operand, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def camp_gemm_fused_w4a4(
    x: jax.Array,          # (M, K) f32/bf16 — quantized to the int4 range
    b_packed: jax.Array,   # (K//2, N) int8 packed int4 weights
    b_scale: jax.Array,    # (1, N) f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    epilogue: str = "none",
    bias: jax.Array | None = None,
    operand: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _camp_gemm_fused(x, b_packed, b_scale, a_bits=4, w_bits=4,
                            block_m=block_m, block_n=block_n, block_k=block_k,
                            out_dtype=out_dtype, epilogue=epilogue, bias=bias,
                            operand=operand, interpret=interpret)
