"""Shared building blocks: norms, rope, linear-with-CAMP, gated MLP.

Tensor-parallel serving: :func:`row_parallel_linear` is the explicit
shard_map call path for the two row-parallel projections of a transformer
block (attention ``wo``, MLP ``w_down``). Each device runs the fused CAMP
GEMM on its K-shard of the weight and the matching slice of the activation,
then the partial outputs are all-reduced — optionally with an int8 payload
on the wire (:func:`repro.parallel.collectives.quantized_psum`). Under an
active ``mode='serve'`` mesh context :func:`gated_mlp` and the attention
output projection route through it automatically when the sharded dim
divides the model axis; otherwise they fall back to the replicated path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.camp import camp_matmul, weight_bits
from repro.core.quant import QuantizedTensor
from repro.kernels.epilogue import apply_epilogue, parse_epilogue
from repro.parallel.collectives import quantized_psum
from repro.parallel.sharding import active_ctx, logical, serve_tp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    # variance reduced in f32, but no (B,S,D) f32 materialization: the only
    # f32 tensor is the (B,S,1) variance (the f32 upcast of x itself would be
    # a multi-GiB live buffer at 32k prefill).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def group_norm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     eps: float = 1e-5) -> jax.Array:
    """Per-head LayerNorm over the last dim. x: (..., H, hd)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def linear(x: jax.Array, w, bias: Optional[jax.Array] = None, *,
           qmode: str = "none", impl: str = "auto",
           epilogue: Optional[str] = None,
           operand: Optional[jax.Array] = None) -> jax.Array:
    """``x @ W (+ b)`` — dispatches to the CAMP quantized pipeline when the
    weight is a :class:`QuantizedTensor`.

    ``epilogue`` appends fused tail stages after the bias (e.g. ``'silu'``,
    ``'gelu'``, ``'mul'``/``'residual'`` with ``operand``); on the quantized
    path they run inside the kernel flush on the f32 accumulator, so the
    activation never round-trips through HBM as a standalone elementwise op.
    """
    stages = []
    if bias is not None:
        stages.append("bias")
    if epilogue and epilogue != "none":
        stages.append(epilogue)
    epi = "+".join(stages) if stages else "none"
    if isinstance(w, QuantizedTensor):
        # The weight's payload decides the kernel family: a caller-side qmode
        # of 'none' (or one whose weight bits disagree with the stored
        # payload, e.g. params quantized separately from cfg.qmode) is
        # remapped to the mode matching the weight — keeping the requested
        # activation treatment (weight-only stays weight-only).
        if qmode == "none" or weight_bits(qmode) != w.bits:
            if qmode.endswith("a16"):
                qmode = "w8a16" if w.bits == 8 else "w4a16"
            else:
                qmode = "w8a8" if w.bits == 8 else "w4a8"
        return camp_matmul(x, w, qmode=qmode, impl=impl, epilogue=epi,
                           bias=bias, operand=operand)
    y = jnp.matmul(x, w.astype(x.dtype))
    if epi != "none":
        y = apply_epilogue(
            y.astype(jnp.float32), parse_epilogue(epi),
            bias=None if bias is None else bias.reshape(1, -1),
            operand=operand).astype(x.dtype)
    return y


def tp_shardable(w, tp: int) -> bool:
    """Can a (K, N) weight's contraction dim split over ``tp`` shards?

    int4 payloads are packed 2-per-byte along K, so each K-shard must also
    hold an even number of logical rows.
    """
    if tp <= 1:
        return False
    k = w.shape[0]
    if k % tp:
        return False
    if isinstance(w, QuantizedTensor) and w.bits == 4:
        return (k // tp) % 2 == 0
    return True


def _tp_int8_reduce() -> bool:
    ctx = active_ctx()
    return bool(ctx is not None and ctx.opts.get("tp_int8_reduce"))


def row_parallel_linear(x: jax.Array, w, *, mesh, axis: str = "model",
                        qmode: str = "none", impl: str = "auto",
                        quantized_reduce: Optional[bool] = None) -> jax.Array:
    """Megatron row-parallel projection: ``x @ W`` with W K-sharded.

    ``x``: (..., K) with the last dim carried by ``axis`` (attention heads ×
    head_dim after head-sharded attention; d_ff after column-parallel
    gate/up); ``w``: (K, N) row-sharded on the same axis. Each device runs
    the fused CAMP GEMM (or bf16 matmul) on its local shard — the activation
    quantization inside the kernel sees only shard-local rows, so no
    quantized operand is ever gathered — and the f32 partial outputs are
    all-reduced, int8-compressed on the wire when ``quantized_reduce``
    (default: the serve context's ``tp_int8_reduce`` opt).
    """
    if quantized_reduce is None:
        quantized_reduce = _tp_int8_reduce()
    if isinstance(w, QuantizedTensor):
        fn = _row_parallel_fn(mesh, axis, x.ndim, qmode, impl,
                              quantized_reduce, w.bits, w.shape[1])
        return fn(x, w.q, w.scale).astype(x.dtype)
    fn = _row_parallel_fn(mesh, axis, x.ndim, qmode, impl, quantized_reduce,
                          None, None)
    return fn(x, w).astype(x.dtype)


# The shard_map programs below are built once per static configuration and
# jitted: a fresh shard_map closure per call would be retraced and
# recompiled on every call of an eagerly driven engine step.
@functools.lru_cache(maxsize=None)
def _row_parallel_fn(mesh, axis, ndim, qmode, impl, quantized_reduce, bits,
                     n):
    xspec = P(*((None,) * (ndim - 1) + (axis,)))
    yspec = P(*((None,) * ndim))

    def reduce(y):
        y = y.astype(jnp.float32)
        return quantized_psum(y, axis) if quantized_reduce \
            else jax.lax.psum(y, axis)

    if bits is None:
        def body(x_l, w_l):
            return reduce(jnp.matmul(x_l, w_l.astype(x_l.dtype)))
        in_specs = (xspec, P(axis, None))
    else:
        def body(x_l, wq_l, ws_l):
            w_l = QuantizedTensor(q=wq_l, scale=ws_l, bits=bits,
                                  shape=(x_l.shape[-1], n))
            return reduce(linear(x_l, w_l, qmode=qmode, impl=impl))
        in_specs = (xspec, P(axis, None), P(None, None))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=yspec, check_vma=False))


def column_linear(x: jax.Array, w, bias: Optional[jax.Array] = None, *,
                  qmode: str = "none", epilogue: Optional[str] = None,
                  operand: Optional[jax.Array] = None) -> jax.Array:
    """``linear`` for a column-parallel weight (q/k/v, gate/up, lm head).

    Under an active ``mode='serve'`` mesh context a quantized weight runs
    its CAMP kernel per device through ``shard_map``: the compiler cannot
    partition a Mosaic kernel, so a kernel fed mesh-placed operands is
    refused on a TPU. Each device multiplies the (replicated) activations by
    its column shard of the weight — the activation quantization sees whole
    rows, so the result equals the single-device GEMM — and the output
    stays sharded along N, with no collective. A weight whose N the model
    axis does not divide (replicated by the serve rules) runs whole on
    every device. Float weights stay with XLA's partitioner.
    """
    mesh, tp = serve_tp()
    if mesh is None or not isinstance(w, QuantizedTensor):
        return linear(x, w, bias, qmode=qmode, epilogue=epilogue,
                      operand=operand)
    axis = "model" if w.shape[1] % tp == 0 else None
    fn = _column_parallel_fn(mesh, axis, x.ndim, qmode, epilogue, w.bits,
                             w.shape[0], w.shape[1] // (tp if axis else 1),
                             bias is not None, operand is not None)
    return fn(x, w.q, w.scale, bias, operand)


@functools.lru_cache(maxsize=None)
def _column_parallel_fn(mesh, axis, ndim, qmode, epilogue, bits, k, n_l,
                        has_bias, has_operand):
    rep = P(*((None,) * ndim))
    col = P(*((None,) * (ndim - 1) + (axis,)))

    def body(x_l, wq_l, ws_l, b_l, o_l):
        w_l = QuantizedTensor(q=wq_l, scale=ws_l, bits=bits, shape=(k, n_l))
        return linear(x_l, w_l, b_l, qmode=qmode, epilogue=epilogue,
                      operand=o_l)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, P(None, axis), P(None, axis),
                  P(axis) if has_bias else None,
                  col if has_operand else None),
        out_specs=col, check_vma=False))


def rope_freqs(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """positions: (...,) int → (cos, sin) of shape (..., head_dim//2), f32."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) → rotated x (half-split)."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


def gated_mlp(x: jax.Array, p: dict, *, qmode: str = "none") -> jax.Array:
    """SiLU-gated FFN (llama-style): down(silu(gate(x)) * up(x)).

    Three fused kernel calls, zero standalone elementwise ops: the gate
    projection applies SiLU in its flush, the up projection multiplies by the
    activated gate in *its* flush, and the down projection is plain. Under a
    serve-mode mesh the gate/up projections run the column-parallel
    shard_map path (weights d_ff-sharded via the logical rules) and the down
    projection the row-parallel one — one all-reduce per MLP.
    """
    g = column_linear(x, p["w_gate"], qmode=qmode, epilogue="silu")
    h = column_linear(x, p["w_up"], qmode=qmode, epilogue="mul", operand=g)
    h = logical(h, "batch", "seq", "d_ff")
    mesh, tp = serve_tp()
    if mesh is not None and tp_shardable(p["w_down"], tp):
        return row_parallel_linear(h, p["w_down"], mesh=mesh, qmode=qmode)
    return linear(h, p["w_down"], qmode=qmode)


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token cross entropy; logits (B,S,V) f32-cast, labels (B,S)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def chunked_xent(h: jax.Array, head, labels: jax.Array, *,
                 n_chunks: int = 8) -> jax.Array:
    """Streamed cross-entropy: never materializes the (B,S,V) logits.

    The (B,S,V) f32 logits (2.5 GiB/device at gb=256×4k with a 152k vocab)
    and the matching f32 lm_head-gradient buffers are the largest training
    allocations. Chunking the vocab with an online logsumexp and `remat`
    around each chunk bounds live memory to one (B,S,V/n) slice — the
    standard fused-xent production trick. Exact (online max-normalized).

    h: (B,S,D) final hidden; head: (D,V) weight (or QuantizedTensor).
    """
    from repro.core.quant import QuantizedTensor
    if isinstance(head, QuantizedTensor):
        head = head.dequantize()
    b, s, d = h.shape
    v = head.shape[-1]
    while v % n_chunks:
        n_chunks -= 1
    vc = v // n_chunks

    def chunk_stats(h_, head_c, labels_, c0):
        logits = jnp.matmul(h_, head_c.astype(h_.dtype)).astype(jnp.float32)
        m = jnp.max(logits, axis=-1)                        # (B,S)
        s_ = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        idx = labels_ - c0
        in_c = (idx >= 0) & (idx < vc)
        gold = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, vc - 1)[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_c, gold, 0.0)
        return m, s_, gold

    chunk_stats = jax.checkpoint(chunk_stats, static_argnums=())

    run_m = jnp.full((b, s), -jnp.inf, jnp.float32)
    run_s = jnp.zeros((b, s), jnp.float32)
    gold_total = jnp.zeros((b, s), jnp.float32)
    for c in range(n_chunks):
        head_c = jax.lax.dynamic_slice_in_dim(head, c * vc, vc, axis=1)
        m, s_, gold = chunk_stats(h, head_c, labels, c * vc)
        new_m = jnp.maximum(run_m, m)
        run_s = run_s * jnp.exp(run_m - new_m) + s_ * jnp.exp(m - new_m)
        run_m = new_m
        gold_total = gold_total + gold
    lse = run_m + jnp.log(run_s)
    return jnp.mean(lse - gold_total)
