"""Operations and bytes against hand counts: at qwen2-0.5b's shape, and at
an untied int4 shape (a quarter of qwen2-72b's widths, 20 layers) for the
counting's int4 and untied-head paths."""
import json
import os

import pytest

import tiny
import counting
from counting import Shape

Q05 = Shape(24, 896, 14, 2, 64, 4864, 151936, 8, True)
Q72 = Shape(20, 8192, 16, 2, 128, 7392, 38016, 4, False)


def test_block_gemms_by_hand():
    # qwen2-0.5b: q 896x896, k and v 896x128, o 896x896, gate/up 896x4864,
    # down 4864x896 -> 14,909,440 weights a block
    kn05 = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert kn05 == 14_909_440
    assert counting.camp_gemms(Q05, 1).ops == 2 * kn05 * 24
    # the int4 shape: q 8192x2048, k and v 8192x256, o 2048x8192,
    # gate/up 8192x7392, down 7392x8192 -> 219,414,528 weights a block
    kn72 = 8192 * 2048 + 2 * 8192 * 256 + 2048 * 8192 + 3 * 8192 * 7392
    assert kn72 == 219_414_528
    assert counting.camp_gemms(Q72, 3).ops == 2 * 3 * kn72 * 20
    # weight bytes: int8 one a weight, packed int4 half; one f32 scale a
    # column; bf16 rows in and out, the bias rows, the up flush's operand
    cols05 = 896 + 2 * 128 + 896 + 2 * 4864 + 896
    bias05 = 2 * (896 + 2 * 128)
    assert cols05 == 12_672
    got = counting.camp_gemms(Q05, 1).bytes / 24
    assert got == kn05 + 4 * cols05 + bias05 + 2 * 4864 + _io(Q05, 1)
    assert counting.camp_gemms(Q72, 1).bytes / 20 == pytest.approx(
        kn72 / 2 + 4 * (2048 + 512 + 8192 + 2 * 7392 + 8192)
        + 2 * (2048 + 512) + 2 * 7392 + _io(Q72, 1))


def _io(s, m):
    """bf16 activations in and out of the seven GEMMs of a block."""
    return 2 * m * sum(k + n for k, n, _ in counting.layer_gemms(s))


def test_heads():
    # tied: the bf16 embedding is the head, outside the CAMP GEMMs
    assert counting.head(Q05, 2).ops == 2 * 2 * 896 * 151936
    assert counting.head(Q05, 1).bytes >= 2 * 896 * 151936
    assert counting.head(Q72, 1).bytes >= 8192 * 38016 / 2
    b = 1e9
    bound = counting.camp_gemm_bound_s(Q72, 4, 4, 393e12, b)
    assert bound == pytest.approx(counting.camp_gemm_bound_s(
        Q72, 4, 0, 393e12, b) + counting.head(Q72, 4).bound_s(393e12, b))
    assert counting.camp_gemm_bound_s(Q05, 4, 4, 393e12, b) == \
        counting.camp_gemm_bound_s(Q05, 4, 0, 393e12, b)


def test_attention_by_hand():
    c = counting.decode_attention(Q05, [1000])
    assert c.ops == 4 * 14 * 64 * 1000 * 24
    assert c.bytes == 24 * (2 * 2 * 1000 * (64 + 4) + 2 * 14 * 64 * 2)
    p = counting.prefill_attention(Q72, 0, 4)
    assert p.ops == 4 * 16 * 128 * (1 + 2 + 3 + 4) * 20
    p = counting.prefill_attention(Q72, 256, 128)
    assert p.ops == 4 * 16 * 128 * (128 * 256 + 128 * 129 // 2) * 20


@pytest.mark.parametrize("name,shape", [("qwen2-0.5b", Q05)])
def test_configuration_files_agree(name, shape):
    with open(os.path.join(tiny.BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert cfg["pool"]["kv_bytes_per_token"] == shape.kv_bytes_per_token
    import harness
    sh = harness._load_module(os.path.join(tiny.BENCH, "configs",
                                           "qwen2_ref.py"),
                              "qwen2_ref").model_shape(cfg)
    assert (sh["n_layers"], sh["d_model"], sh["n_heads"], sh["n_kv_heads"],
            sh["head_dim"], sh["d_ff"], sh["vocab"]) == (
        shape.n_layers, shape.d_model, shape.n_heads, shape.n_kv_heads,
        shape.head_dim, shape.d_ff, shape.vocab)
