"""Jit'd wrappers with implementation dispatch for the CAMP kernels.

Every op exposes ``impl``:

* ``'pallas'`` — the Pallas TPU kernel (``interpret=True`` automatically when
  running on the CPU backend, which is how this container validates them).
* ``'xla'``    — plain XLA int8 ``dot_general`` + scale epilogue. This is what
  the multi-pod dry-run lowers (the CPU backend cannot compile Mosaic), and on
  TPU it is also the fallback XLA would fuse itself.
* ``'hybrid'`` — the paper's §3 hybrid-multiplier decomposition (int8 GEMM as
  four int4-range GEMMs). Bit-exact with 'xla'; exists as the algebraic
  witness of the hardware design.
* ``'ref'``    — the pure-jnp oracle from :mod:`repro.kernels.ref`.

``impl='auto'`` picks 'pallas' on TPU and 'xla' elsewhere.

Common extensions across the GEMM ops:

* ``epilogue=`` / ``bias=`` / ``operand=`` — fused elementwise tails on the
  f32 accumulator (see :mod:`repro.kernels.epilogue`); non-pallas impls apply
  the identical jnp expression after the scale so every impl stays an oracle
  for every other.
* ``block=None`` (the default) — block sizes come from the
  :mod:`repro.core.autotune` cache (seeded by ``choose_blocks``) instead of a
  hardcoded triple.

The ``gemm_*_fused`` family additionally fuses the dynamic activation
quantization *into* the GEMM: callers hand over bf16/f32 activations and the
int8/int4 payload + scales never exist in HBM
(:mod:`repro.kernels.camp_gemm_fused`). Non-pallas impls become a single
jitted quantize→dot→epilogue graph, which XLA fuses — the same HBM-traffic
shape, expressed at the XLA level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core import hybrid as _hybrid
from repro.core.quant import unpack_int4
from repro.kernels import ref as _ref
from repro.kernels.backend import interpret_mode, resolve
from repro.kernels.camp_gemm import camp_gemm_i8 as _pallas_i8
from repro.kernels.camp_gemm_fused import camp_gemm_fused_w4a4 as _pallas_f_a4w4
from repro.kernels.camp_gemm_fused import camp_gemm_fused_w4a8 as _pallas_f_w4
from repro.kernels.camp_gemm_fused import camp_gemm_fused_w8a8 as _pallas_f_i8
from repro.kernels.camp_gemm_w4 import camp_gemm_a4w4 as _pallas_a4w4
from repro.kernels.camp_gemm_w4 import camp_gemm_w4 as _pallas_w4
from repro.kernels.epilogue import (apply_epilogue, parse_epilogue,
                                    validate_epilogue)
from repro.kernels.quantize import quantize_rowwise_kernel as _pallas_quant

_VALID = ("auto", "pallas", "xla", "hybrid", "ref")


def _resolve(impl: str) -> str:
    return resolve(impl, _VALID)


def _blocks(kind, m, n, k, block, *, fused=False, a_in_bytes=4):
    """Explicit block triple, or the autotune cache's pick for this shape."""
    if block is not None:
        return block
    return autotune.get_blocks(kind, m, n, k, fused=fused,
                               a_in_bytes=a_in_bytes)


def _tail(y32, epilogue, bias, operand, out_dtype):
    """Non-pallas epilogue: identical jnp expression to the kernels' flush."""
    y32 = apply_epilogue(y32, parse_epilogue(epilogue),
                         bias=None if bias is None else bias.reshape(1, -1),
                         operand=operand)
    return y32.astype(out_dtype)


def gemm_i8(a_q, b_q, a_scale, b_scale, *, out_dtype=jnp.float32,
            impl: str = "auto", block=None, epilogue: str = "none",
            bias=None, operand=None):
    """CAMP int8 GEMM: (M,K)i8 × (K,N)i8 → (M,N)out_dtype with scale epilogue."""
    impl = _resolve(impl)
    validate_epilogue(epilogue, bias, operand)
    (m, k), n = a_q.shape, b_q.shape[1]
    if impl == "pallas":
        bm, bn, bk = _blocks("i8", m, n, k, block)
        return _pallas_i8(a_q, b_q, a_scale, b_scale, block_m=bm, block_n=bn,
                          block_k=bk, out_dtype=out_dtype, epilogue=epilogue,
                          bias=bias, operand=operand,
                          interpret=interpret_mode())
    if impl == "hybrid":
        acc = _hybrid.hybrid_matmul_i8(a_q, b_q)
    else:  # 'xla' / 'ref'
        acc = _ref.dot_i32(a_q, b_q)
    return _tail(acc.astype(jnp.float32) * (a_scale * b_scale), epilogue,
                 bias, operand, out_dtype)


def gemm_w4(a_q, b_packed, a_scale, b_scale, *, out_dtype=jnp.float32,
            impl: str = "auto", block=None, epilogue: str = "none",
            bias=None, operand=None):
    """CAMP a8w4 GEMM: int8 activations × packed-int4 weights."""
    impl = _resolve(impl)
    validate_epilogue(epilogue, bias, operand)
    (m, k), n = a_q.shape, b_packed.shape[1]
    if impl == "pallas":
        bm, bn, bk = _blocks("w4", m, n, k, block)
        return _pallas_w4(a_q, b_packed, a_scale, b_scale, block_m=bm,
                          block_n=bn, block_k=bk, out_dtype=out_dtype,
                          epilogue=epilogue, bias=bias, operand=operand,
                          interpret=interpret_mode())
    b_q = unpack_int4(b_packed, k)
    if impl == "hybrid":
        acc = _hybrid.hybrid_matmul_w4a8(a_q, b_q)
    else:  # 'xla' / 'ref': unpack outside the (nonexistent) kernel, int8 dot
        acc = _ref.dot_i32(a_q, b_q)
    return _tail(acc.astype(jnp.float32) * (a_scale * b_scale), epilogue,
                 bias, operand, out_dtype)


def gemm_a4w4(a_packed, b_packed, k, a_scale, b_scale, *,
              out_dtype=jnp.float32, impl: str = "auto", block=None,
              epilogue: str = "none", bias=None, operand=None):
    """CAMP int4 GEMM: both operands packed 2-per-byte along K (logical K=k)."""
    impl = _resolve(impl)
    validate_epilogue(epilogue, bias, operand)
    m, n = a_packed.shape[0], b_packed.shape[1]
    if impl == "pallas":
        bm, bn, bk = _blocks("a4w4", m, n, k, block)
        return _pallas_a4w4(a_packed, b_packed, a_scale, b_scale, block_m=bm,
                            block_n=bn, block_k=bk, out_dtype=out_dtype,
                            epilogue=epilogue, bias=bias, operand=operand,
                            interpret=interpret_mode())
    a_q = unpack_int4(a_packed.T, k).T
    b_q = unpack_int4(b_packed, k)
    acc = _ref.dot_i32(a_q, b_q)
    return _tail(acc.astype(jnp.float32) * (a_scale * b_scale), epilogue,
                 bias, operand, out_dtype)


def quantize_rowwise(x, *, bits: int = 8, impl: str = "auto", block_m: int = 256):
    """Fused dynamic rowwise quantization: x → (int8 q, f32 scale (M,1))."""
    impl = _resolve(impl)
    if impl == "pallas":
        return _pallas_quant(x, bits=bits, block_m=block_m,
                             interpret=interpret_mode())
    return _ref.quantize_rowwise_ref(x, bits)


# ---------------------------------------------------------------------------
# Fused activation-quantize + GEMM (+ epilogue): one kernel, one store.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("a_bits", "w4", "hybrid", "out_dtype",
                                    "epilogue"))
def _fused_fallback(x, b, b_scale, bias, operand, *, a_bits, w4, hybrid,
                    out_dtype, epilogue):
    """Single jitted quantize→dot→epilogue graph (XLA fuses the chain).

    ``hybrid=True`` swaps the int32 dot for the paper's §3 hybrid-multiplier
    decomposition so ``impl='hybrid'`` keeps its meaning on the fused path.
    """
    if w4:
        b = unpack_int4(b, x.shape[-1])
    a_q, a_s = _ref.quantize_rowwise_ref(x, a_bits)
    if hybrid:
        acc = (_hybrid.hybrid_matmul_w4a8(a_q, b) if w4
               else _hybrid.hybrid_matmul_i8(a_q, b))
    else:
        acc = _ref.dot_i32(a_q, b)
    return _tail(acc.astype(jnp.float32) * (a_s * b_scale), epilogue, bias,
                 operand, out_dtype)


def _gemm_fused(kind, x, b, b_scale, *, out_dtype, impl, block, epilogue,
                bias, operand):
    impl = _resolve(impl)
    validate_epilogue(epilogue, bias, operand)
    (m, k), n = x.shape, b.shape[1]
    if impl == "pallas":
        bm, bn, bk = _blocks(kind, m, n, k, block, fused=True,
                             a_in_bytes=x.dtype.itemsize)
        fn = {"i8": _pallas_f_i8, "w4": _pallas_f_w4, "a4w4": _pallas_f_a4w4}[kind]
        return fn(x, b, b_scale, block_m=bm, block_n=bn, block_k=bk,
                  out_dtype=out_dtype, epilogue=epilogue, bias=bias,
                  operand=operand, interpret=interpret_mode())
    # a4w4 has no hybrid decomposition (matches the unfused dispatch, which
    # routes every non-pallas a4w4 impl through the ref dot).
    return _fused_fallback(x, b, b_scale, bias, operand,
                           a_bits=(4 if kind == "a4w4" else 8),
                           w4=(kind != "i8"),
                           hybrid=(impl == "hybrid" and kind != "a4w4"),
                           out_dtype=out_dtype, epilogue=epilogue)


def gemm_i8_fused(x, b_q, b_scale, *, out_dtype=jnp.float32,
                  impl: str = "auto", block=None, epilogue: str = "none",
                  bias=None, operand=None):
    """w8a8 with in-kernel activation quantization: (M,K)f × (K,N)i8."""
    return _gemm_fused("i8", x, b_q, b_scale, out_dtype=out_dtype, impl=impl,
                       block=block, epilogue=epilogue, bias=bias,
                       operand=operand)


def gemm_w4_fused(x, b_packed, b_scale, *, out_dtype=jnp.float32,
                  impl: str = "auto", block=None, epilogue: str = "none",
                  bias=None, operand=None):
    """w4a8 with in-kernel activation quantization: (M,K)f × (K//2,N)packed."""
    return _gemm_fused("w4", x, b_packed, b_scale, out_dtype=out_dtype,
                       impl=impl, block=block, epilogue=epilogue, bias=bias,
                       operand=operand)


def gemm_a4w4_fused(x, b_packed, b_scale, *, out_dtype=jnp.float32,
                    impl: str = "auto", block=None, epilogue: str = "none",
                    bias=None, operand=None):
    """w4a4 with in-kernel int4 activation quantization — the packed int4
    activation tensor of the unfused path never exists at all."""
    return _gemm_fused("a4w4", x, b_packed, b_scale, out_dtype=out_dtype,
                       impl=impl, block=block, epilogue=epilogue, bias=bias,
                       operand=operand)
