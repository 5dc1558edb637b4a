"""CAMP packed-int4 GEMM Pallas TPU kernels (a8w4 and a4w4).

The paper's key int4 result is that the hybrid multiplier runs 4-bit GEMMs at
2× the int8 rate with *zero* pack/unpack instruction overhead. The TPU-native
statement of the same idea: int4 weights are stored **2-per-byte in HBM**
(halving the memory-roofline term, which is what actually bounds inference
decode), and the nibble unpack happens *inside* the kernel on VMEM-resident
blocks where it is free relative to the HBM stream it eliminated.

Layouts (see repro.core.quant):
  * weights  (K, N) int4 → packed (K//2, N) int8, low nibble = even k.
  * activations for a4w4: (M, K) int4 → packed (M, K//2) int8 along K.

Like :mod:`repro.kernels.camp_gemm`, both kernels support fused ``epilogue=``
tails on the f32 accumulator and arbitrary (M, N, K) via edge-block padding
(K is padded on the *packed* axis, two zero nibbles per padded byte).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.camp_gemm import _epilogue_inputs
from repro.kernels.epilogue import flush_epilogue, parse_epilogue
from repro.kernels.padding import pad_2d, round_up


def _nibbles(packed):
    """int8 packed bytes → (lo, hi) sign-extended int4-valued int8.

    The shifts run on int32: Mosaic has no int8 shift, and the 32-bit
    arithmetic right shift sign-extends each nibble exactly as
    :func:`repro.core.quant.unpack_int4` does on int8."""
    p = packed.astype(jnp.int32)
    return ((p << 28) >> 28).astype(jnp.int8), (p >> 4).astype(jnp.int8)


def _unpack_k_rows(packed):
    """(bk//2, bn) int8 → (bk, bn) int4-valued int8, sign-extended nibbles."""
    lo, hi = _nibbles(packed)
    # Interleave rows: out[2i] = lo[i], out[2i+1] = hi[i].
    bk2, bn = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * bk2, bn)


def _unpack_k_cols(packed):
    """(bm, bk//2) int8 → (bm, bk): out[:, 2i] = lo, out[:, 2i+1] = hi."""
    lo, hi = _nibbles(packed)
    bm, bk2 = packed.shape
    return jnp.stack([lo, hi], axis=2).reshape(bm, 2 * bk2)


def _camp_gemm_w4_kernel(*refs, stages, n_extra):
    a_ref, b_ref, sa_ref, sb_ref = refs[:4]
    extra = refs[4:4 + n_extra]
    o_ref, acc_ref = refs[4 + n_extra], refs[5 + n_extra]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b_q = _unpack_k_rows(b_ref[...])  # VMEM-resident unpack: no HBM cost
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        flush_epilogue(acc_ref, sa_ref, sb_ref, o_ref, stages, extra)


def _camp_gemm_a4w4_kernel(*refs, stages, n_extra):
    a_ref, b_ref, sa_ref, sb_ref = refs[:4]
    extra = refs[4:4 + n_extra]
    o_ref, acc_ref = refs[4 + n_extra], refs[5 + n_extra]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_q = _unpack_k_cols(a_ref[...])
    b_q = _unpack_k_rows(b_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        a_q, b_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        flush_epilogue(acc_ref, sa_ref, sb_ref, o_ref, stages, extra)


def _even_block_k(block_k: int, k: int) -> int:
    """bk for a packed-K kernel: ≤ k, even (one packed byte = two k's)."""
    bk = min(block_k, k)
    return max(2, bk - (bk % 2))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "epilogue",
                     "interpret"),
)
def camp_gemm_w4(
    a_q: jax.Array,        # (M, K) int8 activations
    b_packed: jax.Array,   # (K//2, N) int8 packed int4 weights
    a_scale: jax.Array,    # (M, 1) f32
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
    m, k = a_q.shape
    kp_rows, n = b_packed.shape
    assert k == 2 * kp_rows, (a_q.shape, b_packed.shape)
    stages = parse_epilogue(epilogue)
    bm, bn = min(block_m, m), min(block_n, n)
    bk = _even_block_k(block_k, k)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)

    a_q = pad_2d(a_q, mp, kp)
    b_packed = pad_2d(b_packed, kp // 2, np_)
    a_scale = pad_2d(a_scale, mp, 1, value=1.0)
    b_scale = pad_2d(b_scale, 1, np_, value=1.0)
    extra, extra_specs = _epilogue_inputs(stages, bias, operand, n=n, bm=bm,
                                          bn=bn, mp=mp, np_=np_)

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_camp_gemm_w4_kernel, stages=stages,
                          n_extra=len(extra)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(a_q, b_packed, a_scale, b_scale, *extra)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "epilogue",
                     "interpret"),
)
def camp_gemm_a4w4(
    a_packed: jax.Array,   # (M, K//2) int8 packed int4 activations
    b_packed: jax.Array,   # (K//2, N) int8 packed int4 weights
    a_scale: jax.Array,    # (M, 1) f32
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
    m, kp_rows = a_packed.shape
    kp_rows2, n = b_packed.shape
    assert kp_rows == kp_rows2, (a_packed.shape, b_packed.shape)
    k = 2 * kp_rows
    stages = parse_epilogue(epilogue)
    bm, bn = min(block_m, m), min(block_n, n)
    bk = _even_block_k(block_k, k)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)

    a_packed = pad_2d(a_packed, mp, kp // 2)
    b_packed = pad_2d(b_packed, kp // 2, np_)
    a_scale = pad_2d(a_scale, mp, 1, value=1.0)
    b_scale = pad_2d(b_scale, 1, np_, value=1.0)
    extra, extra_specs = _epilogue_inputs(stages, bias, operand, n=n, bm=bm,
                                          bn=bn, mp=mp, np_=np_)

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_camp_gemm_a4w4_kernel, stages=stages,
                          n_extra=len(extra)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(a_packed, b_packed, a_scale, b_scale, *extra)
    return out[:m, :n]
