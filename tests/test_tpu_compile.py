"""The main-path kernels compile for a TPU v5e (described, not attached).

Interpret mode never applies Mosaic's rules — block shapes that must be
(8, 128)-aligned or full, which ops legalize, how much VMEM a kernel may
use — so every kernel the serving path launches is compiled here for one
chip of a described ``v5e:2x2`` topology, at qwen2-0.5b's published widths
and the blocks autotune picks for them, and for qwen2-72b's tensor-parallel
(tp=4) shard shapes. Each case asserts that the compiled program holds the
Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library at a time, and every
xdist worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import autotune
from repro.kernels.camp_gemm_fused import (camp_gemm_fused_w4a4,
                                           camp_gemm_fused_w4a8,
                                           camp_gemm_fused_w8a8)
from repro.kernels.paged_attention import _paged_attention_pallas
from repro.kernels.paged_prefill import _paged_prefill_pallas

QWEN = get_config("qwen2-0.5b")
Q72 = get_config("qwen2-72b")
TP = 4
# one device's attention shape: (kv heads, query heads per kv head, hd)
ATTN = {"qwen2-0.5b": (QWEN.n_kv_heads, QWEN.n_heads // QWEN.n_kv_heads,
                       QWEN.hd),
        "qwen2-72b/tp4": (Q72.n_kv_heads // TP, Q72.n_heads // Q72.n_kv_heads,
                          Q72.hd)}


def _prefill_params(model, ps):
    """(chunk, pages_per_step) the engine picks for this model and page."""
    kv, g, hd = ATTN[model]
    return autotune.get_prefill_params(kv * (TP if "tp" in model else 1), hd,
                                       ps, mean_len=4096, group=g, save=False)


CHUNK = _prefill_params("qwen2-0.5b", 16)[0]

_FUSED = {"i8": camp_gemm_fused_w8a8, "w4": camp_gemm_fused_w4a8,
          "a4w4": camp_gemm_fused_w4a4}


def _linears(cfg, tp=1):
    """(K, N) of one device's q, kv, out, up/gate and down projections."""
    d, hq, hkv = cfg.d_model, cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd
    return {"q": (d, hq // tp), "kv": (d, hkv // tp), "out": (hq // tp, d),
            "up": (d, cfg.d_ff // tp), "down": (cfg.d_ff // tp, d)}


GEMM_CASES = (
    [("qwen2-0.5b", kind, m, name, kn)
     for kind in _FUSED for m in (1, CHUNK)
     for name, kn in _linears(QWEN).items()]
    + [("qwen2-72b/tp4", kind, 8, name, kn)
       for kind in ("i8", "w4") for name, kn in _linears(Q72, TP).items()])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, args, sharding):
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
              for s, dt in args]
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "model,kind,m,name,kn", GEMM_CASES,
    ids=[f"{c[0]}-{c[1]}-m{c[2]}-{c[3]}" for c in GEMM_CASES])
def test_fused_gemm_compiles(one_chip, model, kind, m, name, kn):
    k, n = kn
    bm, bn, bk = autotune.tune(kind, m, n, k, fused=True, a_in_bytes=2,
                               measure=False, save=False)
    rows = k if kind == "i8" else k // 2

    def fn(x, b, s):
        return _FUSED[kind](x, b, s, block_m=bm, block_n=bn, block_k=bk,
                            interpret=False)

    text = _compiled_text(fn, [((m, k), jnp.bfloat16), ((rows, n), jnp.int8),
                               ((1, n), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def _pool_shapes(kv, hd, ps, num_pages):
    return [((num_pages, kv, ps, hd), jnp.int8)] * 2 + \
        [((num_pages, kv, ps), jnp.float32)] * 2


@pytest.mark.parametrize("model", list(ATTN))
@pytest.mark.parametrize("ps", [16, 128])
@pytest.mark.parametrize("batch", [1, 8])
def test_paged_decode_compiles(one_chip, model, ps, batch):
    kv, g, hd = ATTN[model]
    max_pages = -(-1000 // ps)
    args = ([((batch, kv, g, hd), jnp.bfloat16)]
            + _pool_shapes(kv, hd, ps, 64 * batch)
            + [((batch, max_pages), jnp.int32), ((batch,), jnp.int32)])
    text = _compiled_text(_paged_attention_pallas, args, one_chip)
    assert "tpu_custom_call" in text


# q_start 37 with 5 rows: an unaligned speculative verify panel (γ = 4);
# the others are the first and a later page-aligned prefill chunk.
@pytest.mark.parametrize("model", list(ATTN))
@pytest.mark.parametrize("ps", [16, 128])
@pytest.mark.parametrize("q_start", [0, 37, 1024])
def test_paged_prefill_compiles(one_chip, model, ps, q_start):
    kv, g, hd = ATTN[model]
    chunk, pp = _prefill_params(model, ps)
    c = 5 if q_start % ps else chunk
    max_pages = -(-(q_start + c) // ps) + 1

    def fn(q, kp, vp, ks, vs, table):
        return _paged_prefill_pallas(q, kp, vp, ks, vs, table,
                                     q_start=q_start, pages_per_step=pp)

    args = ([((kv, c, g, hd), jnp.bfloat16)] + _pool_shapes(kv, hd, ps, 128)
            + [((max_pages,), jnp.int32)])
    assert "tpu_custom_call" in _compiled_text(fn, args, one_chip)
