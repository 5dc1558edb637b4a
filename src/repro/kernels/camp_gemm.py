"""CAMP int8 GEMM Pallas TPU kernel.

This is the paper's `camp` instruction lifted to MXU granularity:

* paper: one instruction consumes a 4×16 A-panel (column-major) and a 16×4
  B-panel (row-major) and accumulates a 4×4 int32 tile in an auxiliary
  register, `kc/16` times, before one store.
* here: one grid step consumes a (bm×bk) A-block and a (bk×bn) B-block from
  VMEM and accumulates a (bm×bn) int32 tile in a VMEM scratch accumulator,
  K/bk times, before one store — with the **Cartesian scale epilogue**
  (outer product of per-row × per-column scales) fused into the flush.

The GotoBLAS blocking hierarchy of the paper (L3→L2→L1→registers) becomes
HBM→VMEM→VREG→MXU: ``BlockSpec`` index maps stream panels of A and B through
VMEM exactly like the 5-loop GotoBLAS schedule streams panels through caches,
and the int32 accumulator plays the auxiliary register. See
``repro.core.blocking`` for the block-size selection (the `kc/mc/nR` analogue)
and ``repro.core.autotune`` for the measured/modelled selection cache.

Two extensions over the bare paper kernel:

* ``epilogue=`` — elementwise tails (bias/silu/gelu/residual/mul, see
  :mod:`repro.kernels.epilogue`) applied to the f32 accumulator inside the
  flush, preserving the one-store property through bias-add and activations.
* arbitrary (M, N, K) — edge blocks are zero-padded to the block lattice and
  the result sliced back (:mod:`repro.kernels.padding`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (epilogue_needs, flush_epilogue,
                                    parse_epilogue)
from repro.kernels.padding import pad_2d, round_up


def _camp_gemm_kernel(*refs, stages, n_extra):
    """One (i, j, k) grid step: acc += A_blk · B_blk; flush on the last k."""
    a_ref, b_ref, sa_ref, sb_ref = refs[:4]
    extra = refs[4:4 + n_extra]
    o_ref, acc_ref = refs[4 + n_extra], refs[5 + n_extra]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The CAMP outer-product-accumulate: int8 × int8 → int32 on the MXU.
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        # Cartesian (outer-product) scale epilogue: s_a ⊗ s_b.
        flush_epilogue(acc_ref, sa_ref, sb_ref, o_ref, stages, extra)


def _epilogue_inputs(stages, bias, operand, *, n, bm, bn, mp, np_):
    """Pad the optional epilogue tensors; → (arrays, specs).

    Presence mismatches were already rejected by ``validate_epilogue`` at the
    dispatch layer; direct kernel callers get the same check here.
    """
    needs_bias, needs_opd = epilogue_needs(stages)
    if needs_bias != (bias is not None) or needs_opd != (operand is not None):
        raise ValueError(f"epilogue stages {stages} require bias={needs_bias},"
                         f" operand={needs_opd}")
    arrays, specs = [], []
    if needs_bias:
        arrays.append(pad_2d(bias.reshape(1, n), 1, np_))
        specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if needs_opd:
        arrays.append(pad_2d(operand, mp, np_))
        specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
    return arrays, specs


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "epilogue",
                     "interpret"),
)
def camp_gemm_i8(
    a_q: jax.Array,           # (M, K) int8
    b_q: jax.Array,           # (K, N) int8
    a_scale: jax.Array,       # (M, 1) f32
    b_scale: jax.Array,       # (1, N) f32
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    epilogue: str = "none",
    bias: jax.Array | None = None,      # (N,) when 'bias' in epilogue
    operand: jax.Array | None = None,   # (M, N) when 'residual'/'mul'
    interpret: bool = False,
) -> jax.Array:
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2, (a_q.shape, b_q.shape)
    stages = parse_epilogue(epilogue)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)

    a_q = pad_2d(a_q, mp, kp)
    b_q = pad_2d(b_q, kp, np_)
    a_scale = pad_2d(a_scale, mp, 1, value=1.0)
    b_scale = pad_2d(b_scale, 1, np_, value=1.0)
    extra, extra_specs = _epilogue_inputs(stages, bias, operand, n=n, bm=bm,
                                          bn=bn, mp=mp, np_=np_)

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_camp_gemm_kernel, stages=stages, n_extra=len(extra)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
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
    )(a_q, b_q, a_scale, b_scale, *extra)
    return out[:m, :n]
