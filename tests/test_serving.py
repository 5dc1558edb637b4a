"""Serving engine: generation determinism, quantized-vs-bf16 agreement,
int8 KV cache accuracy, batched requests, paged-int8 decode parity and
continuous batching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import forward, init_params, quantize_params
from repro.serving.engine import (ContinuousBatchingEngine, build_decode_step,
                                  build_prefill_step, generate,
                                  init_serve_caches)
from repro.serving.kv_cache import PagePool


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_generate_deterministic_greedy(model):
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    t1 = generate(params, cfg, prompt, steps=8)
    t2 = generate(params, cfg, prompt, steps=8)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_quantized_generation_close(model):
    """w8a8 serving should track bf16 greedy decoding for most tokens."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                cfg.vocab_size)
    base = np.asarray(generate(params, cfg, prompt, steps=8))
    qp = quantize_params(params, cfg, "w8a8")
    q = np.asarray(generate(qp, cfg, prompt, steps=8, ))
    agree = (base == q).mean()
    assert agree > 0.5, f"w8a8 token agreement only {agree:.2f}"


def test_int8_kv_cache_close_to_bf16(model):
    cfg, params = model
    b, s = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    pre = build_prefill_step(cfg)
    caches_bf = init_serve_caches(cfg, b, 32)
    caches_i8 = init_serve_caches(cfg, b, 32, kv_dtype="int8")
    logits_bf, caches_bf = pre(params, toks, caches_bf)
    logits_i8, caches_i8 = pre(params, toks, caches_i8)
    # prefill logits identical (cache not read during prefill)
    np.testing.assert_allclose(np.asarray(logits_bf, np.float32),
                               np.asarray(logits_i8, np.float32), rtol=1e-2,
                               atol=1e-2)
    dec = build_decode_step(cfg)
    tok = jnp.argmax(logits_bf, -1)[:, None].astype(jnp.int32)
    t_bf, _ = dec(params, caches_bf, tok, jnp.int32(s))
    t_i8, _ = dec(params, caches_i8, tok, jnp.int32(s))
    assert (np.asarray(t_bf) == np.asarray(t_i8)).mean() >= 0.5


def test_prefill_last_logits_match_full_forward(model):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                              cfg.vocab_size)
    full, _, _ = forward(params, cfg, toks)
    pre = build_prefill_step(cfg)
    last, _ = pre(params, toks, init_serve_caches(cfg, 2, 16))
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(full[:, -1], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_batched_requests_isolated(model):
    """Each batch row's generation must only depend on its own prompt."""
    cfg, params = model
    p1 = jax.random.randint(jax.random.PRNGKey(5), (1, 16), 0, cfg.vocab_size)
    p2 = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0, cfg.vocab_size)
    both = jnp.concatenate([p1, p2], axis=0)
    solo = np.asarray(generate(params, cfg, p1, steps=6))
    batched = np.asarray(generate(params, cfg, both, steps=6))
    np.testing.assert_array_equal(batched[0], solo[0])


@pytest.mark.parametrize("n_kv", [1, 2, 4])   # MQA / GQA / MHA
def test_paged_int8_decode_parity_vs_f32_dense(n_kv):
    """Paged int8-KV decode logits track the dense f32-cache reference."""
    cfg = get_config("qwen2-0.5b", reduced=True, dtype="float32",
                     n_heads=4, n_kv_heads=n_kv, head_dim=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s, steps, ps = 2, 12, 4, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    caches = init_serve_caches(cfg, b, s + steps)            # dense f32
    last, caches = build_prefill_step(cfg)(params, toks, caches)

    pool = PagePool(n_layers=cfg.n_layers, n_kv_heads=n_kv, head_dim=cfg.hd,
                    num_pages=4 * b * ((s + steps) // ps + 1), page_size=ps,
                    quantized=True, dtype=jnp.float32)
    for row in range(b):
        pool.reserve(row, s + steps)
        for i, layer in enumerate(caches):
            pool.ingest(row, i, layer["attn"].k[row:row + 1, :, :s],
                        layer["attn"].v[row:row + 1, :, :s])

    tok = jnp.argmax(last.astype(jnp.float32), -1)[:, None].astype(jnp.int32)
    for step in range(steps):
        logits_d, caches, _ = forward(params, cfg, tok, caches=caches,
                                      cache_pos=jnp.int32(s + step))
        tables, lengths = pool.batch_tables(list(range(b)))
        pcaches = [{"attn": pool.layer_cache(i, tables, lengths)}
                   for i in range(cfg.n_layers)]
        logits_p, new_p, _ = forward(params, cfg, tok,
                                     positions=lengths[:, None],
                                     caches=pcaches)
        for i, layer in enumerate(new_p):
            pool.writeback(i, layer["attn"])
        for row in range(b):
            pool.lens[row] += 1
        np.testing.assert_allclose(
            np.asarray(logits_p, np.float32), np.asarray(logits_d, np.float32),
            rtol=2e-2, atol=2e-2,
            err_msg=f"n_kv={n_kv} decode step {step}")
        # drive both paths with the same next token
        tok = jnp.argmax(logits_d[:, -1].astype(jnp.float32),
                         -1)[:, None].astype(jnp.int32)


def test_continuous_batching_mixed_trace_matches_solo(model):
    """Sequences entering/leaving mid-flight decode exactly as when alone."""
    cfg, params = model
    specs = [(5, 6), (12, 4), (8, 10), (3, 3), (16, 5)]     # (prompt, max_new)
    prompts = [jax.random.randint(jax.random.PRNGKey(10 + i), (n,), 0,
                                  cfg.vocab_size)
               for i, (n, _) in enumerate(specs)]

    def make_engine():
        # 8 pages of 8 tokens < the 11 pages the trace needs in total →
        # admission is staggered and relies on mid-flight page reclamation
        return ContinuousBatchingEngine(params, cfg, kv_dtype="int8",
                                        page_size=8, capacity_tokens=64)

    eng = make_engine()
    sids = [eng.submit(prompts[i], mx) for i, (_, mx) in enumerate(specs)]
    mixed = eng.run()
    assert set(mixed) == set(sids)
    for i, (n, mx) in enumerate(specs):
        assert len(mixed[sids[i]]) == mx
        solo_eng = make_engine()
        sid = solo_eng.submit(prompts[i], mx)
        solo = solo_eng.run()[sid]
        assert mixed[sids[i]] == solo, f"request {i} diverged under batching"
    assert eng.pool.num_free == eng.pool.num_pages   # all pages reclaimed


def test_prefix_sharing_page_accounting_and_parity(model):
    """N sequences sharing a P-token prefix hold ~P/page_size shared pages
    (not N·P/page_size), decode identically to unshared solo runs, and
    release everything on finish."""
    cfg, params = model
    n, prefix_len, tail_len, ps = 4, 32, 8, 8
    prefix = jax.random.randint(jax.random.PRNGKey(20), (prefix_len,), 0,
                                cfg.vocab_size)
    prompts = [jnp.concatenate([
        prefix, jax.random.randint(jax.random.PRNGKey(21 + i), (tail_len,),
                                   0, cfg.vocab_size)]) for i in range(n)]

    eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8", page_size=ps,
                                   capacity_tokens=8 * 64)
    sids = [eng.submit(p, 16) for p in prompts]
    while eng.waiting or eng.prefilling:          # drive until all admitted
        eng.step()
    stats = eng.pool.shared_page_stats()
    shared_pages = prefix_len // ps
    assert stats["shared_slots"] == shared_pages
    # n tables reference the prefix chain; (n-1)·P/ps pages were saved
    assert (stats["table_entries"] - stats["distinct_slots"]
            == (n - 1) * shared_pages)
    outs = eng.run()
    assert eng.pool.num_free == eng.pool.num_pages    # decref'd clean
    for i, sid in enumerate(sids):
        solo = ContinuousBatchingEngine(params, cfg, kv_dtype="int8",
                                        page_size=ps, capacity_tokens=8 * 64)
        ssid = solo.submit(prompts[i], 16)
        assert solo.run()[ssid] == outs[sid], f"request {i} diverged"


def test_engine_rejects_oversized_request():
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8",
                                   page_size=8, capacity_tokens=16)
    eng.submit(jnp.zeros((8,), jnp.int32), 32)       # needs 5 pages, pool has 2
    with pytest.raises(RuntimeError):
        eng.run()


def test_temperature_sampling_runs(model):
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0,
                                cfg.vocab_size)
    toks = generate(params, cfg, prompt, steps=4, key=jax.random.PRNGKey(0),
                    sample="temperature", temperature=0.8)
    assert toks.shape == (2, 4)
    assert (np.asarray(toks) >= 0).all()
    assert (np.asarray(toks) < cfg.vocab_size).all()


def test_engine_spans_tile_prompts_and_match_the_batch(model):
    """With a tracer, each prompt's prefill chunks tile [matched, len) with
    no gap, each decode's rows are the batch the step decodes, forward and
    sample sit inside a lane span, and greedy tokens do not change."""
    from repro.serving.tracing import Tracer
    cfg, params = model
    prefix = jax.random.randint(jax.random.PRNGKey(30), (24,), 0,
                                cfg.vocab_size)
    prompts = [jnp.concatenate([prefix, jax.random.randint(
        jax.random.PRNGKey(31 + i), (n,), 0, cfg.vocab_size)])
        for i, n in enumerate((13, 5, 30))]

    def serve(tracer):
        eng = ContinuousBatchingEngine(params, cfg, kv_dtype="int8",
                                       page_size=8, prefill_chunk=16,
                                       capacity_tokens=8 * 64, tracer=tracer)
        sids = [eng.submit(p, 4 + 2 * i) for i, p in enumerate(prompts)]
        return sids, eng.run()

    tracer = Tracer(enabled=True)
    sids, traced = serve(tracer)
    _, plain = serve(None)
    assert traced == plain
    spans = tracer.spans
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["serve.admit"]) == len(prompts)
    assert [a.attrs["matched"] for a in by["serve.admit"]] == [0, 24, 24]
    for a, sid, p in zip(by["serve.admit"], sids, prompts):
        assert a.attrs["seq_id"] == sid and a.attrs["prompt_tokens"] == len(p)
        chunks = [c.attrs for c in by["serve.prefill_chunk"]
                  if c.attrs["seq_id"] == sid]
        edges = [a.attrs["matched"]] + [c["q_start"] + c["tokens"]
                                        for c in chunks]
        assert [c["q_start"] for c in chunks] == edges[:-1]
        assert edges[-1] == len(p)
        assert [c["last"] for c in chunks] == [False] * (len(chunks) - 1) \
            + [True]
    for d in by["serve.decode"]:
        step = spans[d.parent]
        assert step.name == "serve.step"
        finished = sum(1 for c in by["serve.prefill_chunk"]
                       if c.parent == d.parent and c.attrs["last"])
        assert d.attrs["rows"] == step.attrs["active"] + finished
        assert len(d.attrs["seq_ids"]) == d.attrs["rows"]
        assert d.attrs["max_pages"] >= 1
    lanes = {"serve.prefill_chunk", "serve.decode"}
    for s in by["serve.forward"] + by["serve.sample"]:
        assert spans[s.parent].name in lanes
    assert all(s.parent == -1 for s in by["serve.step"])
