#!/usr/bin/env python3
"""Smoke run of the quantized serving path on a TPU.

    python chip_smoke.py            # one chip: kernel parity, then qwen2-0.5b
    python chip_smoke.py --tp 4     # four chips: qwen2-72b w4a8, tp=4

One chip, in order:

1. Kernel parity: the fused w8a8 / w4a8 / w4a4 CAMP GEMMs, the paged decode
   kernel and the paged prefill kernel, each run with ``impl='pallas'``
   against ``impl='xla'`` on the same seeded operands at qwen2-0.5b's
   widths, within the tolerances stated beside each check.
2. Serving: qwen2-0.5b at its published widths (24 layers, d 896, vocab
   151936) with random w8a8 weights from ``--seed``, an int8 paged pool and
   greedy sampling, through ``ContinuousBatchingEngine`` — the path of
   ``python -m repro.launch.serve``. Requests of different lengths: one
   long enough for several prefill chunks, two that share a prefix page,
   a short one; then one request on an engine with n-gram speculation
   (unaligned verify writes and rollback). The workload runs twice on
   fresh engines: the first pass pays the compiles, the second is steady.
   Checks: every request answered, the engine's first-step logits against
   the dense forward, page accounting back to empty, and that every kernel
   on the path resolved to compiled Pallas.

With ``--tp 4`` only the four-chip phase runs: qwen2-72b at w4a8, loaded
layer by layer onto a (1, 4) mesh; its first layers at tp=4 against tp=1 on
one device (logits and greedy tokens), then the full model serving a few
requests, with the bytes each device holds.

Everything else is printed first; the last line of standard output is one
JSON object naming the device. Without a TPU the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GEMM_TOL = 1e-2     # max |pallas - xla| / max |xla|
ATTN_TOL = 2e-2     # same, paged attention (f32 softmax, MXU f32 dots)
# ||served - dense|| / ||dense|| of first-step logits: int8 per-token KV
# against the dense forward's bf16 KV, through every layer of a random
# model (6.7e-2 on a v5e at qwen2-0.5b widths, 3.0e-2 on the CPU at the
# reduced config); a wrong page, head or scale gives O(1)
LOGITS_TOL = 0.15
# tp=4 vs tp=1 over the same int8 pool: the row-parallel GEMMs quantize
# shard-local activations (3.1e-2 on the CPU at tiny widths)
TP_LOGITS_TOL = 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel_max(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), "non-finite kernel output"
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _rel_rms(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), "non-finite logits"
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


# ---------------------------------------------------------------------------
# Phase 1: kernel parity
# ---------------------------------------------------------------------------
def kernel_parity(cfg, seed: int) -> dict:
    """Each main-path kernel with impl='pallas' vs impl='xla'; {name: err}."""
    import jax
    import jax.numpy as jnp

    from repro.core.camp import prepare_weight
    from repro.kernels import ops
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.paged_prefill import paged_prefill_attention
    from repro.serving.engine import paged_shape

    rng = np.random.default_rng(seed)
    errs = {}
    d, f = cfg.d_model, cfg.d_ff
    fused = {"w8a8": ops.gemm_i8_fused, "w4a8": ops.gemm_w4_fused,
             "w4a4": ops.gemm_a4w4_fused}
    ps, chunk, pp = paged_shape(cfg)
    for qmode, fn in fused.items():
        for m, (k, n) in ((8, (d, f)), (chunk, (f, d))):
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            w = prepare_weight(jnp.asarray(
                rng.standard_normal((k, n)) * k ** -0.5, jnp.float32), qmode)
            got = fn(x, w.q, w.scale, impl="pallas")
            want = fn(x, w.q, w.scale, impl="xla")
            errs[f"gemm_{qmode}_m{m}_k{k}_n{n}"] = (_rel_max(got, want),
                                                    GEMM_TOL)

    kv, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    n_pages = 64

    def pool():
        def ints():
            return jnp.asarray(rng.integers(-127, 128, (n_pages, kv, ps, hd)),
                               jnp.int8)

        def scales():
            return jnp.asarray(rng.uniform(1e-3, 5e-2, (n_pages, kv, ps)),
                               jnp.float32)
        return ints(), ints(), scales(), scales()

    batch, max_pages = 8, 4
    q = jnp.asarray(rng.standard_normal((batch, kv, g, hd)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(n_pages)[:batch * max_pages]
                         .reshape(batch, max_pages), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, max_pages * ps + 1, batch),
                          jnp.int32)
    kp, vp, ks, vs = pool()
    got = paged_attention(q, kp, vp, ks, vs, tables, lengths, impl="pallas")
    want = paged_attention(q, kp, vp, ks, vs, tables, lengths, impl="xla")
    errs[f"paged_decode_b{batch}_ps{ps}"] = (_rel_max(got, want), ATTN_TOL)

    table = jnp.asarray(rng.permutation(n_pages), jnp.int32)
    for q_start, c in ((0, chunk), (ps + 37, 5)):   # chunk; verify panel
        q = jnp.asarray(rng.standard_normal((kv, c, g, hd)), jnp.bfloat16)
        kw = dict(q_start=q_start, pages_per_step=pp)
        got = paged_prefill_attention(q, kp, vp, ks, vs, table,
                                      impl="pallas", **kw)
        want = paged_prefill_attention(q, kp, vp, ks, vs, table, impl="xla",
                                       **kw)
        errs[f"paged_prefill_q{q_start}_c{c}_ps{ps}"] = (
            _rel_max(got, want), ATTN_TOL)
    jax.block_until_ready(got)
    return errs


# ---------------------------------------------------------------------------
# Phase 2: serving
# ---------------------------------------------------------------------------
def _requests(cfg, ps: int, chunk: int, seed: int):
    """[(prompt, max_new)]: multi-chunk, two sharing a prefix page, short."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()

    shared = toks(ps + ps // 4)
    return [(toks(2 * chunk + ps // 2 + 3), 8),    # several prefill chunks
            (shared, 8),
            (shared[:ps] + toks(ps // 2 + 1), 8),  # shares page 0 of shared
            (toks(17), 8)]


def _kernel_witness() -> dict:
    """How many programs each main-path Pallas kernel has compiled."""
    from repro.kernels.camp_gemm_fused import (camp_gemm_fused_w4a4,
                                               camp_gemm_fused_w4a8,
                                               camp_gemm_fused_w8a8)
    from repro.kernels.paged_attention import _paged_attention_pallas
    from repro.kernels.paged_prefill import _paged_prefill_pallas
    fns = {"fused_gemm_w8a8": camp_gemm_fused_w8a8,
           "fused_gemm_w4a8": camp_gemm_fused_w4a8,
           "fused_gemm_w4a4": camp_gemm_fused_w4a4,
           "paged_decode": _paged_attention_pallas,
           "paged_prefill": _paged_prefill_pallas}
    return {name: fn._cache_size() for name, fn in fns.items()}


def _check_pool_empty(eng) -> None:
    pool = eng.pool
    pool.check_invariants()
    assert not pool.tables and not pool.lens, "sequences left in the pool"
    assert pool.num_free == pool.num_pages, \
        f"{pool.num_pages - pool.num_free} pages still held"


def serve_smoke(cfg, *, seed: int = 0, log=log) -> dict:
    """Serve ``cfg`` (qmode w8a8, int8 pool) through the engine; returns the
    counts and checks (raises on a failed check)."""
    import jax

    from repro.kernels import ops
    from repro.kernels.backend import interpret_mode, resolve, resolve_paged
    from repro.launch.serve import init_serving_params
    from repro.models.transformer import forward
    from repro.serving.engine import ContinuousBatchingEngine, paged_shape
    from repro.serving.kv_cache import round_up
    from repro.serving.spec_decode import SpecConfig

    cfg = dataclasses.replace(cfg, qmode="w8a8")
    t0 = time.perf_counter()
    params = init_serving_params(jax.random.PRNGKey(seed), cfg, "w8a8")
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    ps, chunk, _ = paged_shape(cfg)
    reqs = _requests(cfg, ps, chunk, seed)
    pattern = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, 6).tolist()
    spec_prompt, spec_new = pattern * 5, 16
    capacity = sum(round_up(len(p) + n, ps) for p, n in reqs) \
        + round_up(len(spec_prompt) + spec_new + 8, ps) + 2 * ps

    def engine(spec=None):
        return ContinuousBatchingEngine(
            params, cfg, kv_dtype="int8", page_size=ps,
            capacity_tokens=capacity, sample="greedy",
            key=jax.random.PRNGKey(seed), spec=spec)

    def run(eng, work):
        """Serve ``work``; returns (token streams, most pages shared)."""
        sids = [eng.submit(np.asarray(p, np.int32), n) for p, n in work]
        shared = 0
        while eng.step():
            shared = max(shared, eng.pool.shared_page_stats()["shared_slots"])
        for sid, (p, n) in zip(sids, work):
            out = eng.finished[sid].tokens
            assert len(out) == n, f"request {sid}: {len(out)}/{n} tokens"
            assert all(0 <= t < cfg.vocab_size for t in out), out
        _check_pool_empty(eng)
        return [eng.finished[s].tokens for s in sids], shared

    passes, streams = [], None
    for _ in range(2):      # pass 1 compiles; pass 2 reuses every program
        eng = engine()
        t0 = time.perf_counter()
        got, shared = run(eng, reqs)
        passes.append(time.perf_counter() - t0)
        assert streams is None or got == streams, "passes disagree"
        streams = got
    assert shared >= 1, "the prefix page was not shared"
    n_tokens = sum(len(s) for s in streams)

    # the served path's first-step logits vs the dense forward (XLA
    # attention over the unquantized KV, same GEMMs)
    prompt = np.asarray(reqs[1][0], np.int32)
    served = eng.prefill_logits(prompt)
    dense, _, _ = forward(params, cfg, jax.numpy.asarray(prompt)[None],
                          last_logits_only=True)
    logits_err = _rel_rms(served, np.asarray(dense[0, -1], np.float32))
    _check_pool_empty(eng)

    spec_eng = engine(SpecConfig(method="ngram", gamma=4))
    t0 = time.perf_counter()
    spec_out = run(spec_eng, [(spec_prompt, spec_new)])[0][0]
    t_spec = time.perf_counter() - t0
    spec = spec_eng.spec_summary()
    plain_out = run(engine(), [(spec_prompt, spec_new)])[0][0]
    agree = sum(a == b for a, b in zip(spec_out, plain_out))

    resolved = {"fused_gemm": resolve("auto", ops._VALID),
                "paged_decode": resolve_paged("auto", True),
                "paged_prefill": resolve_paged("auto", True),
                "interpret": interpret_mode()}
    out = {"model": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
           "vocab": cfg.vocab_size, "page_size": ps, "chunk": chunk,
           "requests": len(reqs) + 1, "tokens": n_tokens + len(spec_out),
           "weights_s": t_weights, "first_pass_s": passes[0],
           "steady_pass_s": passes[1], "spec_s": t_spec,
           "logits_rel_rms": logits_err, "shared_pages": shared,
           "spec": {k: spec[k] for k in ("spec_steps", "proposed",
                                         "accepted", "emitted")},
           "spec_vs_plain_agree": f"{agree}/{len(spec_out)}",
           "resolved": resolved, "kernel_programs": _kernel_witness()}
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, w8a8, int8 pool ps={ps}, chunk={chunk}")
    log(f"[serve] {out['requests']} requests, {out['tokens']} tokens, "
        f"{shared} prefix page(s) shared; "
        f"weights {t_weights:.3f}s, first pass (compiles) {passes[0]:.3f}s, "
        f"steady pass {passes[1]:.3f}s, speculative {t_spec:.3f}s")
    log(f"[serve] served vs dense first-step logits rel rms {logits_err:.3e}"
        f" (limit {LOGITS_TOL})")
    log(f"[serve] spec {out['spec']}, stream vs plain decode "
        f"{out['spec_vs_plain_agree']}")
    log(f"[serve] resolved {resolved}; programs {out['kernel_programs']}")
    assert logits_err <= LOGITS_TOL, f"served logits rel err {logits_err}"
    assert spec["spec_steps"] > 0, "no verify step ran"
    return out


# ---------------------------------------------------------------------------
# Four chips: qwen2-72b w4a8 at tp=4
# ---------------------------------------------------------------------------
def device_bytes(tree) -> dict:
    """{device id: bytes of ``tree`` that device holds}."""
    import jax
    per = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(per.items()))


def tp_smoke(cfg, tp: int, *, seed: int = 0, cut_layers: int = 2,
             qmode: str = "w4a8", log=log) -> dict:
    """``cfg`` at ``qmode`` on a (1, tp) mesh: its first ``cut_layers``
    layers at tp vs tp=1 on one device, then the whole model serving."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import init_serving_params
    from repro.serving.engine import ContinuousBatchingEngine

    cfg = dataclasses.replace(cfg, qmode=qmode)
    mesh = make_serving_mesh(tp)
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (300, 45, 9)]

    def engine(params, c, m):
        return ContinuousBatchingEngine(params, c, kv_dtype="int8",
                                        capacity_tokens=4096, mesh=m,
                                        key=key)

    # parity: the same first layers, sharded over tp devices vs one device
    log(f"[tp] mesh {dict(mesh.shape)}; parity at {cut_layers} layers")
    cut = dataclasses.replace(cfg, n_layers=cut_layers)
    p_tp = init_serving_params(key, cut, qmode, mesh)
    e_tp = engine(p_tp, cut, mesh)
    assert e_tp.tp == tp and e_tp.pool.sharded, "kv heads not sharded"
    p_one = jax.device_put(p_tp, jax.devices()[0])
    e_one = engine(p_one, cut, None)
    lg_tp, lg_one = (e.prefill_logits(prompts[1]) for e in (e_tp, e_one))
    logits_err = _rel_rms(lg_tp, lg_one)
    outs = []
    for e in (e_tp, e_one):
        sids = [e.submit(np.asarray(p, np.int32), 6) for p in prompts[1:]]
        res = e.run()
        outs.append([t for s in sids for t in res[s]])
        _check_pool_empty(e)
    agree = float(np.mean([a == b for a, b in zip(*outs)]))
    log(f"[tp] {cfg.name} first {cut_layers} layers (depth cut from "
        f"{cfg.n_layers}), tp={tp} vs tp=1: first-step logits rel rms "
        f"{logits_err:.3e} (limit {TP_LOGITS_TOL}); greedy tokens agree "
        f"{agree:.2f}")
    assert logits_err <= TP_LOGITS_TOL, f"tp logits rel err {logits_err}"
    assert agree >= 0.5, f"tp greedy agreement {agree}"
    del p_tp, e_tp, p_one, e_one

    log(f"[tp] loading all {cfg.n_layers} layers")
    t0 = time.perf_counter()
    params = init_serving_params(key, cfg, qmode, mesh)
    jax.block_until_ready(params)
    t_load = time.perf_counter() - t0
    per_dev = device_bytes(params)
    log(f"[tp] {cfg.name} {qmode}: {cfg.n_layers} layers loaded in "
        f"{t_load:.3f}s; weight bytes per device {per_dev}")
    assert len(per_dev) == tp and min(per_dev.values()) > 0.2 * max(
        per_dev.values()), f"weights not spread over {tp} devices"
    eng = engine(params, cfg, mesh)
    t0 = time.perf_counter()
    sids = [eng.submit(np.asarray(p, np.int32), 6) for p in prompts]
    res = eng.run()
    t_serve = time.perf_counter() - t0
    n_tokens = sum(len(res[s]) for s in sids)
    assert n_tokens == 6 * len(prompts), res
    _check_pool_empty(eng)
    in_use = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        in_use[d.id] = (stats.get("bytes_in_use"),
                        stats.get("peak_bytes_in_use"))
    log(f"[tp] served {len(prompts)} requests, {n_tokens} tokens in "
        f"{t_serve:.3f}s (compiles included); device (bytes_in_use, peak) "
        f"{in_use}")
    return {"logits_rel_rms": logits_err, "token_agree": agree,
            "cut_layers": cut_layers, "load_s": t_load,
            "weight_bytes": per_dev, "tokens": n_tokens,
            "serve_s": t_serve, "memory": in_use}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="4: run only the four-chip qwen2-72b phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[smoke] {len(devices)} x {dev.device_kind}; compile cache "
        f"{enable_compile_cache()}")
    if args.tp > 1:
        if len(devices) < args.tp:
            print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, "
                  f"found {len(devices)}", file=sys.stderr)
            return 1
        tp_smoke(get_config("qwen2-72b"), args.tp, seed=args.seed)
    else:
        cfg = get_config("qwen2-0.5b")
        t0 = time.perf_counter()
        errs = kernel_parity(cfg, args.seed)
        for name, (err, tol) in errs.items():
            log(f"[parity] {name}: max rel err {err:.3e} (limit {tol})")
        bad = {n: e for n, (e, tol) in errs.items() if not e <= tol}
        assert not bad, f"kernel parity failed: {bad}"
        log(f"[parity] {len(errs)} checks passed in "
            f"{time.perf_counter() - t0:.3f}s")
        out = serve_smoke(cfg, seed=args.seed)
        want = {"fused_gemm": "pallas", "paged_decode": "pallas",
                "paged_prefill": "pallas", "interpret": False}
        assert out["resolved"] == want, out["resolved"]
        idle = [k for k, v in out["kernel_programs"].items()
                if v == 0 and k in ("fused_gemm_w8a8", "paged_decode",
                                    "paged_prefill")]
        assert not idle, f"kernels never compiled on the served path: {idle}"
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"[smoke] peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
