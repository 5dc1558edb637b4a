"""Tensor-parallel serving: serve-mode sharding rules, TP shard logic,
autotune-warmup dedupe, and the 8-virtual-device parity suite.

The multi-device checks (sharded vs single-device decode/prefill logits,
engine page accounting, indivisible-head fallback) live in
``tests/tp_parity_check.py`` and run one subprocess per check with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the repo
convention for device-count overrides (they must not leak into the pytest
session). The in-process tests below need no mesh devices at all.
"""
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.core import autotune
from repro.parallel.sharding import make_rules, serve_tp, spec_for


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = _FakeMesh({"data": 2, "model": 4})


def test_serve_rules_shard_kv_heads_not_seq():
    rules = make_rules("serve")
    # paged pool pages: (P, KV, ps, hd) — kv heads carry model, pages don't
    spec = spec_for((64, 4, 8, 16), ("kv_pages", "kv_heads", None, None),
                    rules, MESH)
    assert spec == P(None, "model", None, None)
    # decode q (B, KV, G, hd)
    spec = spec_for((8, 4, 2, 16), ("batch", "kv_heads", None, "head_dim"),
                    rules, MESH)
    assert spec == P("data", "model", None, None)
    # serve mode never splits the KV sequence dim (pages are head-sharded)
    spec = spec_for((8, 4096, 16), ("batch", "seq_kv", None), rules, MESH)
    assert spec == P("data", None, None)


def test_serve_rules_indivisible_heads_replicate():
    rules = make_rules("serve")
    spec = spec_for((64, 3, 8, 16), ("kv_pages", "kv_heads", None, None),
                    rules, MESH)
    assert spec == P(None, None, None, None)


def test_serve_tp_inactive_without_context():
    mesh, tp = serve_tp()
    assert mesh is None and tp == 1


def test_tp_shardable_packed_int4():
    import jax.numpy as jnp
    from repro.core.camp import prepare_weight
    from repro.models.modules import tp_shardable

    w = jnp.zeros((24, 16), jnp.float32)
    assert tp_shardable(w, 4)                    # 24 % 4 == 0
    assert not tp_shardable(w, 5)
    w4 = prepare_weight(w, "w4a8")
    assert tp_shardable(w4, 4)                   # 6 logical rows/shard, even
    assert not tp_shardable(w4, 8)               # 3 rows/shard: splits a pack
    w4b = prepare_weight(jnp.zeros((20, 16), jnp.float32), "w4a8")
    assert tp_shardable(w4b, 2)                  # 10/shard, pack-aligned
    assert not tp_shardable(w4b, 4)              # 5/shard: splits a pack


def test_warm_gemm_autotune_dedupes_and_warms_tp_shards(tmp_path,
                                                        monkeypatch):
    from repro.configs import get_config
    from repro.serving.engine import warm_gemm_autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    autotune.clear_cache()
    cfg = get_config("qwen2-0.5b", reduced=True, qmode="w8a8",
                     n_heads=8, n_kv_heads=4)
    tuned = warm_gemm_autotune(cfg, batch_sizes=(1, 8))
    assert tuned
    # the same warmup again is a no-op: every shape is already cached
    assert warm_gemm_autotune(cfg, batch_sizes=(1, 8)) == []
    # tp=2 warms the *shard* shapes (on a fresh cache so none collide with
    # the replicated shapes above): row-parallel wo runs K/2, column-
    # parallel q/kv proj run N/2
    autotune.clear_cache(disk=True)
    tp_tuned = warm_gemm_autotune(cfg, batch_sizes=(1, 8), tp=2)
    kns = {(k, n) for ((m, n, k), _) in tp_tuned}
    d, hhd, kvhd = cfg.d_model, cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd
    assert (hhd // 2, d) in kns                  # wo row shard
    assert (d, kvhd // 2) in kns                 # kv column shard
    assert (hhd, d) not in kns                   # unsharded wo NOT warmed
    # and repeating the tp warmup is also fully deduped
    assert warm_gemm_autotune(cfg, batch_sizes=(1, 8), tp=2) == []
    autotune.clear_cache()


def test_engine_without_mesh_is_single_device():
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ContinuousBatchingEngine

    cfg = get_config("qwen2-0.5b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8", page_size=8,
                                   capacity_tokens=64)
    assert eng.tp == 1 and eng.mesh is None and not eng.pool.sharded


# check name in tests/tp_parity_check.py → the markers it prints
TP_CHECKS = {"prefill_decode": ("PREFILL_OK", "DECODE_OK"),
             "engine": ("ENGINE_OK",), "indivisible": ("INDIV_OK",),
             "quantized": ("QUANT_OK",), "spec": ("SPEC_OK",)}


@pytest.mark.parametrize("check", list(TP_CHECKS))
def test_tp_parity_subprocess(check):
    """One 8-virtual-device parity check per case (sharded decode/prefill
    logits, engine page accounting, the indivisible-head fallback, the
    quantized TP GEMMs, speculative decoding), each in its own subprocess
    with its own time limit."""
    script = os.path.join(os.path.dirname(__file__), "tp_parity_check.py")
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    res = subprocess.run([sys.executable, script, check],
                         capture_output=True, text=True, timeout=400,
                         env=env)
    for marker in TP_CHECKS[check] + ("TP_PARITY_OK",):
        assert marker in res.stdout, \
            (marker, res.stdout[-1000:], res.stderr[-3000:])
