"""Operations and bytes of the logical work a serving step needs.

Counted from the model's shapes and the tokens a step processed, in the
formats the weights and the cache are stored in (int8 = 1 B, packed int4 =
0.5 B, f32 scales, bf16 activations). Nothing here reads a kernel's block
choice or padding, so a kernel change cannot move its own yardstick.

``Shape`` is one chip's share of the model as it is run (heads, d_ff and
vocabulary already divided by the tensor-parallel degree).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Tuple

ACT_BYTES = 2          # bf16 activations in and out of every GEMM
SCALE_BYTES = 4        # f32 per-channel and per-token scales


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weight_bits: int           # 8 (w8a8) or 4 (w4a8, w4a4)
    tied_head: bool            # tied heads run a bf16 matmul, not the CAMP GEMM
    qkv_bias: bool = True

    @property
    def kv_bytes_per_token(self) -> int:
        """Pool bytes one cached token occupies over all layers: int8 k and
        v rows plus one f32 scale per (head, token) for each."""
        per_layer = 2 * self.n_kv_heads * (self.head_dim + SCALE_BYTES)
        return self.n_layers * per_layer


@dataclasses.dataclass
class Cost:
    ops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Cost") -> "Cost":
        self.ops += other.ops
        self.bytes += other.bytes
        return self

    def bound_s(self, peak_ops: float, peak_bytes_per_s: float) -> float:
        """Least time the chip could take: the larger of the two bounds."""
        return max(self.ops / peak_ops, self.bytes / peak_bytes_per_s)


def layer_gemms(s: Shape) -> List[Tuple[int, int, str]]:
    """(K, N, extra) of one block's CAMP GEMMs; ``extra`` names what the
    kernel reads besides A and W: a bias row or the gate's output (the up
    projection multiplies by it in its flush)."""
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    bias = "bias" if s.qkv_bias else ""
    return [(s.d_model, q, bias), (s.d_model, kv, bias), (s.d_model, kv, bias),
            (q, s.d_model, ""), (s.d_model, s.d_ff, ""),
            (s.d_model, s.d_ff, "operand"), (s.d_ff, s.d_model, "")]


def gemm(m: int, k: int, n: int, weight_bits: int, extra: str = "") -> Cost:
    """One (m, k) x (k, n) GEMM: weights streamed once with their scales,
    activations in and out once."""
    b = k * n * weight_bits / 8 + n * SCALE_BYTES + (m * k + m * n) * ACT_BYTES
    if extra == "bias":
        b += n * ACT_BYTES
    elif extra == "operand":
        b += m * n * ACT_BYTES
    return Cost(2.0 * m * k * n, b)


def camp_gemms(s: Shape, m: int) -> Cost:
    """The blocks' CAMP GEMMs of one forward over ``m`` rows, all layers
    (the head is counted apart)."""
    c = Cost()
    for k, n, extra in layer_gemms(s):
        c += gemm(m, k, n, s.weight_bits, extra)
    c.ops *= s.n_layers
    c.bytes *= s.n_layers
    return c


def head(s: Shape, m: int) -> Cost:
    """The vocabulary head over ``m`` rows (bf16 weights when tied)."""
    wb = 16 if s.tied_head else s.weight_bits
    return gemm(m, s.d_model, s.vocab, wb)


def decode_attention(s: Shape, lengths: Iterable[int]) -> Cost:
    """Paged decode over sequences holding ``lengths`` tokens (the new one
    included), all layers: q.k and p.v for every query head, each cached
    int8 k/v row and its scale read once per kv head."""
    c = Cost()
    qo = 2 * s.n_heads * s.head_dim * ACT_BYTES
    for L in lengths:
        c.ops += 4.0 * s.n_heads * s.head_dim * L
        c.bytes += 2 * s.n_kv_heads * L * (s.head_dim + SCALE_BYTES) + qo
    c.ops *= s.n_layers
    c.bytes *= s.n_layers
    return c


def prefill_attention(s: Shape, q_start: int, chunk: int) -> Cost:
    """Causal paged prefill of ``chunk`` queries at ``q_start``, all layers:
    query p attends to p + 1 keys; the cached prefix and the chunk's own
    rows are read once per kv head."""
    keys = chunk * q_start + chunk * (chunk + 1) // 2
    ops = 4.0 * s.n_heads * s.head_dim * keys
    kv_len = q_start + chunk
    b = (2 * s.n_kv_heads * kv_len * (s.head_dim + SCALE_BYTES)
         + 2 * chunk * s.n_heads * s.head_dim * ACT_BYTES)
    return Cost(ops * s.n_layers, b * s.n_layers)


def model_ops(s: Shape, rows: int, head_rows: int, attn: Cost) -> float:
    """Model operations of ``rows`` tokens through every layer, the head on
    ``head_rows`` of them, plus the attention work ``attn``."""
    return camp_gemms(s, rows).ops + head(s, head_rows).ops + attn.ops


def camp_gemm_bound_s(s: Shape, m: int, head_rows: int, peak_ops: float,
                      peak_bytes_per_s: float) -> float:
    """Least time of one forward's CAMP GEMM calls over ``m`` rows (the
    untied head's over ``head_rows``), each call bounded on its own."""
    if m <= 0:
        return 0.0
    per_layer = sum(gemm(m, k, n, s.weight_bits, extra).bound_s(
        peak_ops, peak_bytes_per_s) for k, n, extra in layer_gemms(s))
    t = s.n_layers * per_layer
    if head_rows and not s.tied_head:
        t += head(s, head_rows).bound_s(peak_ops, peak_bytes_per_s)
    return t
