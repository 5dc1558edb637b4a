"""Sharded-vs-single-device serving parity driver (run as a script).

    python tests/tp_parity_check.py [check ...]     # default: every check

Spawned by ``tests/test_tp_serving.py`` (one subprocess per check) and by
the CI sharded-serving job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the mesh has
8 virtual CPU devices; the device-count override must never leak into the
pytest session, hence the subprocess. Checks (names in ``CHECKS``):

* PREFILL_OK — every chunk's last-position logits of a chunked paged
  prefill match between a head-sharded pool under the serve mesh context
  (shard_map kernels, interpret-mode Pallas) and a replicated pool.
* DECODE_OK  — per-step ragged decode logits match the same way.
* ENGINE_OK  — a mixed continuous-batching workload (prefix sharing,
  staggered admission) produces identical tokens AND bit-identical pool
  accounting (block tables, lens, shared-page stats, free/retained counts).
* INDIV_OK   — a kv-head count indivisible by the model axis degrades to
  replicated attention (engine tp == 1, pool unsharded) with identical
  tokens.
* QUANT_OK   — the quantized TP GEMM paths: ``row_parallel_linear`` with an
  int8 and a packed-int4 QuantizedTensor (K-shard slicing) tracks the
  single-device fused CAMP result, ``quantized_psum`` is exact to one
  shared quantization step, and a w8a8 engine with ``tp_int8_reduce`` keeps
  majority token agreement with its single-device run.
* SPEC_OK    — speculative decoding under the mesh: the γ+1-token verify
  panels run through the head-sharded ``paged_prefill_attention_tp`` path
  (drafting stays replicated), greedy token streams and draft/accept stats
  are identical to both the sharded non-speculative engine and the
  single-device speculative engine, and rollback leaves the replicated
  page accounting bit-for-bit equal.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_serving_mesh
from repro.models import init_params
from repro.models.transformer import forward
from repro.parallel.sharding import make_rules, mesh_context
from repro.serving.engine import ContinuousBatchingEngine
from repro.serving.kv_cache import PagePool

PS = 8          # page size
CHUNK = 16      # prefill chunk (page-aligned)
STEPS = 4       # decode steps in the manual loop
TP = 4

CFG = get_config("qwen2-0.5b", n_layers=2, d_model=64, n_heads=8,
                 n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512,
                 max_seq_len=128, dtype="float32")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
MESH = make_serving_mesh(TP)
RULES = make_rules("serve")


def serve_scope():
    return mesh_context(MESH, RULES, mode="serve")


def make_pool(mesh):
    return PagePool(n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
                    head_dim=CFG.hd, num_pages=64, page_size=PS,
                    quantized=True, dtype=jnp.float32, mesh=mesh)


def chunked_prefill(pool, prompt, scope):
    """Engine-shaped chunked paged prefill; returns each chunk's last
    logits."""
    sid = 0
    s = int(prompt.shape[0])
    pool.reserve(sid, s + STEPS)
    outs, pos = [], 0
    while pos < s:
        c = min(CHUNK, s - pos)
        toks = prompt[None, pos:pos + c]
        positions = (pos + jnp.arange(c))[None]
        caches = [{"attn": pool.prefill_cache(i, sid, pos, 2)}
                  for i in range(CFG.n_layers)]
        with scope():
            lg, new, _ = forward(PARAMS, CFG, toks, positions=positions,
                                 caches=caches, last_logits_only=True)
        for i, layer in enumerate(new):
            pool.writeback(i, layer["attn"])
        pool.lens[sid] = pos + c
        outs.append(np.asarray(lg[:, -1], np.float32))
        pos += c
    return outs


def decode_steps(pool, tok, scope):
    """Manual ragged decode loop; returns per-step logits."""
    outs = []
    for _ in range(STEPS):
        pool.ensure_writable(0, pool.lens[0] // PS)
        tables, lengths = pool.batch_tables([0])
        caches = [{"attn": pool.layer_cache(i, tables, lengths)}
                  for i in range(CFG.n_layers)]
        with scope():
            lg, new, _ = forward(PARAMS, CFG, tok, positions=lengths[:, None],
                                 caches=caches)
        for i, layer in enumerate(new):
            pool.writeback(i, layer["attn"])
        pool.lens[0] += 1
        last = np.asarray(lg[:, -1], np.float32)
        outs.append(last)
        tok = jnp.asarray(last.argmax(-1)[:, None], jnp.int32)
    return outs


def check_prefill_decode():
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3 * CHUNK - 4,), 0,
                                CFG.vocab_size)
    pool_r = make_pool(None)
    pool_s = make_pool(MESH)
    assert pool_s.sharded
    shards = pool_s.k_pages[0].addressable_shards
    assert {tuple(sh.data.shape) for sh in shards} == \
        {(64, CFG.n_kv_heads // TP, PS, CFG.hd)}, "pages not head-sharded"

    ref = chunked_prefill(pool_r, prompt, contextlib.nullcontext)
    got = chunked_prefill(pool_s, prompt, serve_scope)
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4,
                                   err_msg=f"prefill chunk {i}")
    print("PREFILL_OK")

    tok = jnp.asarray(ref[-1].argmax(-1)[:, None], jnp.int32)
    ref_d = decode_steps(pool_r, tok, contextlib.nullcontext)
    got_d = decode_steps(pool_s, tok, serve_scope)
    for i, (a, b) in enumerate(zip(ref_d, got_d)):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4,
                                   err_msg=f"decode step {i}")
    print("DECODE_OK")


def engine_state(eng):
    """The replicated host-side accounting that must match bit-for-bit."""
    return {
        "tables": dict(eng.pool.tables),
        "lens": dict(eng.pool.lens),
        "stats": eng.pool.shared_page_stats(),
        "free": eng.pool.num_free,
        "retained": eng.pool.num_retained,
    }


def run_engine(cfg, params, prompts, mesh, *, snap_at: int):
    eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8", page_size=PS,
                                   capacity_tokens=512, mesh=mesh)
    sids = [eng.submit(p, 6) for p in prompts]
    snap = None
    steps = 0
    while eng.step():
        steps += 1
        if steps == snap_at:
            snap = engine_state(eng)
    outs = {s: eng.finished[s].tokens for s in sids}
    return outs, snap, engine_state(eng), eng


def check_engine():
    key = jax.random.PRNGKey(2)
    prefix = jax.random.randint(key, (2 * PS,), 0, CFG.vocab_size)
    prompts = [jnp.concatenate([
        prefix,
        jax.random.randint(jax.random.fold_in(key, i), (5 + 3 * i,), 0,
                           CFG.vocab_size)]) for i in range(3)]
    ref, ref_mid, ref_end, _ = run_engine(CFG, PARAMS, prompts, None,
                                          snap_at=4)
    got, got_mid, got_end, eng = run_engine(CFG, PARAMS, prompts, MESH,
                                            snap_at=4)
    assert eng.tp == TP and eng.pool.sharded
    assert ref == got, f"tokens diverged: {ref} vs {got}"
    assert ref_mid == got_mid, "mid-flight page accounting diverged"
    assert ref_end == got_end, "final page accounting diverged"
    assert ref_mid["stats"]["shared_slots"] > 0, "prefix sharing inactive"
    assert ref_end["retained"] > 0, "trie retention inactive after release"
    print("ENGINE_OK")


def check_indivisible():
    cfg = get_config("qwen2-0.5b", n_layers=2, d_model=60, n_heads=6,
                     n_kv_heads=3, head_dim=16, d_ff=128, vocab_size=512,
                     max_seq_len=128, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [jax.random.randint(jax.random.PRNGKey(9 + i), (10 + 3 * i,),
                                  0, cfg.vocab_size) for i in range(2)]
    ref, _, ref_end, _ = run_engine(cfg, params, prompts, None, snap_at=2)
    got, _, got_end, eng = run_engine(cfg, params, prompts, MESH, snap_at=2)
    assert eng.tp == 1 and not eng.pool.sharded, \
        "3 kv heads must degrade to replicated under model=4"
    assert ref == got and ref_end == got_end
    print("INDIV_OK")


def check_quantized():
    from jax.sharding import PartitionSpec as P

    from repro.core.camp import prepare_weight
    from repro.models import quantize_params
    from repro.models.modules import linear, row_parallel_linear
    from repro.parallel.collectives import quantized_psum

    rng = np.random.default_rng(5)
    # quantized_psum: exact integer sum, one shared quantization step
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    f = jax.shard_map(lambda a: quantized_psum(a, "model"), mesh=MESH,
                      in_specs=P(None, "model"), out_specs=P(None, None),
                      check_vma=False)
    want = x.reshape(16, TP, 32 // TP).transpose(1, 0, 2).sum(0)
    step = float(jnp.max(jnp.abs(x))) / 127.0
    assert np.abs(np.asarray(f(x)) - np.asarray(want)).max() <= TP * step

    # row_parallel_linear on QuantizedTensor weights (int8 and packed int4,
    # exercising the K-shard slicing of the packed payload), with and
    # without the int8-wire reduce, vs the single-device fused CAMP GEMM
    xx = jnp.asarray(rng.standard_normal((3, 5, 64)), jnp.float32)
    for qmode in ("w8a8", "w4a8"):
        wq = prepare_weight(
            jnp.asarray(rng.standard_normal((64, 32)), jnp.float32), qmode)
        ref = np.asarray(linear(xx, wq, qmode=qmode), np.float32)
        span = np.abs(ref).max()
        for wire in (False, True):
            got = np.asarray(row_parallel_linear(
                xx, wq, mesh=MESH, qmode=qmode, quantized_reduce=wire),
                np.float32)
            assert np.abs(got - ref).max() <= 0.05 * span, \
                f"{qmode} wire={wire}: rel err {np.abs(got-ref).max()/span}"

    # w8a8 engine end to end with the int8-compressed all-reduce
    cfg = get_config("qwen2-0.5b", n_layers=2, d_model=64, n_heads=8,
                     n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512,
                     max_seq_len=128, qmode="w8a8")
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), cfg,
                             "w8a8")
    prompts = [jax.random.randint(jax.random.PRNGKey(30 + i), (10 + 4 * i,),
                                  0, cfg.vocab_size) for i in range(2)]

    def run(mesh, wire):
        eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8",
                                       page_size=PS, capacity_tokens=512,
                                       mesh=mesh, tp_int8_reduce=wire)
        sids = [eng.submit(p, 6) for p in prompts]
        outs = eng.run()
        return [t for s in sids for t in outs[s]], eng

    ref, _ = run(None, False)
    got, eng = run(MESH, True)
    assert eng.tp == TP and eng.pool.sharded
    agree = np.mean([a == b for a, b in zip(ref, got)])
    assert agree >= 0.5, f"w8a8+int8-wire token agreement {agree}"
    print("QUANT_OK")


def check_spec():
    from repro.serving.spec_decode import SpecConfig

    key = jax.random.PRNGKey(4)
    pattern = jax.random.randint(key, (6,), 0, CFG.vocab_size)
    prompts = [jnp.tile(pattern, 5),                 # repetitive: drafts land
               jax.random.randint(jax.random.fold_in(key, 1), (13,), 0,
                                  CFG.vocab_size)]   # random: drafts miss

    def run(mesh, spec):
        eng = ContinuousBatchingEngine(PARAMS, CFG, kv_dtype="int8",
                                       page_size=PS, capacity_tokens=512,
                                       mesh=mesh, spec=spec)
        sids = [eng.submit(p, 10) for p in prompts]
        outs = eng.run()
        return [outs[s] for s in sids], engine_state(eng), eng

    spec = lambda: SpecConfig(method="ngram", gamma=3)  # noqa: E731
    base, base_end, _ = run(None, None)
    ref, ref_end, ref_eng = run(None, spec())
    got, got_end, eng = run(MESH, spec())
    assert eng.tp == TP and eng.pool.sharded
    assert ref == base, "single-device spec diverged from plain decode"
    assert got == ref, f"sharded spec tokens diverged: {ref} vs {got}"
    assert got_end == ref_end == base_end, "page accounting diverged"
    r, g = ref_eng.spec_summary(), eng.spec_summary()
    assert r == g, f"spec stats diverged: {r} vs {g}"
    assert g["proposed"] > 0 and g["accepted"] > 0, "speculation inactive"
    print("SPEC_OK")


CHECKS = {"prefill_decode": check_prefill_decode, "engine": check_engine,
          "indivisible": check_indivisible, "quantized": check_quantized,
          "spec": check_spec}


if __name__ == "__main__":
    assert len(jax.devices()) >= 8, "needs 8 virtual devices (XLA_FLAGS)"
    for name in sys.argv[1:] or CHECKS:
        CHECKS[name]()
    print("TP_PARITY_OK")
