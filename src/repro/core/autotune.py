"""Shape-keyed block-size autotuning for the CAMP GEMM kernels.

``choose_blocks`` (the GotoBLAS-analog analytic pick) is a good seed but a
single hardcoded block triple cannot be right for both a 1-token decode GEMM
and a 32k-row prefill GEMM. This module makes block selection a **cache**:

* key — (kernel kind, fused?, M, N, K, backend),
* candidates — ``choose_blocks`` seed plus its neighborhood (bk halved and
  doubled, register tile halved and doubled), filtered by VMEM fit,
* scoring — on a live TPU backend each candidate is timed on synthetic
  operands (median of a few reps); under ``interpret`` / on non-TPU backends
  an analytic roofline model (HBM stream bytes per the kernels' actual
  BlockSpec revisit pattern + MXU flops + per-grid-step overhead) picks the
  winner instead, so tuning is instant and deterministic in tests,
* persistence — winners are written through to a JSON cache
  (``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``) so a serving
  process never re-tunes a shape another process already paid for.

``ops.gemm_*`` and ``camp_matmul`` use :func:`get_blocks` whenever the caller
does not pass an explicit block triple. Measurement only happens outside jit
tracing (shapes are static there anyway; inside a trace the analytic model or
cache answers).
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Callable, Optional, Tuple

from repro.core.blocking import MXU, VMEM_BYTES, BlockConfig, choose_blocks

# v5e roofline constants (same as benchmarks/common.py; duplicated because
# src/ must not import the benchmarks/ harness package).
_PEAK_INT8 = 394e12   # int8 MXU FLOP/s per chip
_HBM_BW = 819e9       # B/s
_STEP_OVERHEAD_S = 1e-6  # per-grid-step issue overhead; penalizes tiny blocks

KINDS = ("i8", "w4", "a4w4")
_KIND_BITS = {"i8": (8, 8), "w4": (4, 8), "a4w4": (4, 4)}  # (w_bits, a_bits)

_lock = threading.Lock()
_mem_cache: dict = {}
_disk_loaded = False


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def clear_cache(*, disk: bool = False) -> None:
    global _disk_loaded
    with _lock:
        _mem_cache.clear()
        _disk_loaded = False
        if disk:
            try:
                os.remove(cache_path())
            except OSError:
                pass


def _load_disk() -> None:
    """Merge the JSON cache into memory once per process (under _lock)."""
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(cache_path()) as f:
            on_disk = json.load(f)
    except (OSError, ValueError):
        return
    for key, entry in on_disk.items():
        _mem_cache.setdefault(key, entry)


def _save_disk() -> None:
    """Atomic read-merge-write of the JSON cache (under _lock); best-effort."""
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        merged = {}
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            pass
        merged.update(_mem_cache)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc. — the in-memory cache still works


def _backend() -> str:
    import jax
    return jax.default_backend()


def _key(kind: str, fused: bool, m: int, n: int, k: int, backend: str,
         a_in_bytes: int) -> str:
    # a_in_bytes only shapes the fused kernels' VMEM row panel; unfused
    # kernels stream quantized activations, so it stays out of their key.
    f = f"fused-a{a_in_bytes}B" if fused else "unfused"
    return f"{kind}|{f}|m{m}|n{n}|k{k}|{backend}"


# Scoped VMEM the fused kernels' in-kernel int4 unpack (int32 nibble shifts
# plus the row interleave) takes per unpacked weight element: the v5e
# compiler asks for 33.3 MB at bk×bn = 4096×256 and 16.7 MB at 2048×256.
_W4_UNPACK_VMEM_PER_ELEM = 32


def _fits(kind: str, fused: bool, block: Tuple[int, int, int], k: int,
          a_in_bytes: int, budget: int = VMEM_BYTES // 2) -> bool:
    bm, bn, bk = block
    w_bits, a_bits = _KIND_BITS[kind]
    if fused:
        # Fused kernels hold the full (bm, K) activation row-panel in its
        # storage dtype plus the int8 working block, B double-buffered, the
        # int32 accumulator, the f32 output tile and the (bm, 1) scales;
        # packed-int4 weights add the unpack temporaries, which must fit
        # the scoped VMEM limit together with the rest.
        from repro.kernels.padding import round_up
        kp = round_up(k, bk)
        a = bm * kp * a_in_bytes + bm * bk
        b = 2 * (bk * bn * w_bits // 8)
        need = a + b + bm * bn * 8 + bm * 4
        unpack = _W4_UNPACK_VMEM_PER_ELEM * bk * bn if w_bits == 4 else 0
        return need <= budget and need + unpack <= VMEM_BYTES
    return BlockConfig(bm, bn, bk).vmem_bytes(w_bits, a_bits) <= budget


def candidates(kind: str, m: int, n: int, k: int, *, fused: bool = False,
               a_in_bytes: int = 4) -> list:
    """Seed from choose_blocks, then explore its blocking neighborhood."""
    w_bits, a_bits = _KIND_BITS[kind]
    seed = choose_blocks(m, n, k, w_bits=w_bits, a_bits=a_bits)
    cands = []

    def add(bm, bn, bk):
        bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
        bm, bn, bk = max(bm, 1), max(bn, 1), max(bk, 1)
        if kind != "i8":
            bk = max(2, bk - bk % 2)  # packed-K kernels need even bk
        blk = (bm, bn, bk)
        if blk not in cands and _fits(kind, fused, blk, k, a_in_bytes):
            cands.append(blk)

    add(seed.bm, seed.bn, seed.bk)
    add(seed.bm, seed.bn, seed.bk * 2)
    add(seed.bm, seed.bn, max(MXU, seed.bk // 2))
    add(seed.bm * 2, seed.bn * 2, seed.bk)
    add(max(MXU, seed.bm // 2), max(MXU, seed.bn // 2), seed.bk)
    add(max(MXU, seed.bm // 2), seed.bn, seed.bk * 2)
    if fused and not cands:
        # Large-K fused panel: shrink bm until the row-panel fits VMEM.
        bm = seed.bm
        while bm > 1:
            bm //= 2
            add(bm, seed.bn, seed.bk)
            if cands:
                break
    if not cands:
        bm, bn, bk = min(seed.bm, m), min(seed.bn, n), min(seed.bk, k)
        if kind != "i8":
            bk = max(2, bk - bk % 2)
        cands.append((bm, bn, bk))  # last resort: seed, budget notwithstanding
    return cands


def model_time_s(kind: str, m: int, n: int, k: int,
                 block: Tuple[int, int, int], *, fused: bool = False,
                 a_in_bytes: int = 4) -> float:
    """Analytic v5e time for one GEMM under this blocking.

    HBM bytes follow the kernels' BlockSpec revisit pattern: B is re-streamed
    once per grid row (M/bm); unfused A is re-streamed once per grid column
    (N/bn); the fused A row-panel's index map is constant in (j, k), so it
    streams exactly once.
    """
    from repro.kernels.padding import round_up
    bm, bn, bk = block
    w_bits, a_bits = _KIND_BITS[kind]
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    steps = (mp // bm) * (np_ // bn) * (kp // bk)
    if fused:
        a_bytes = mp * kp * a_in_bytes                      # once per i-row
    else:
        a_bytes = mp * kp * (a_bits / 8) * (np_ // bn)      # once per j-col
    b_bytes = kp * np_ * (w_bits / 8) * (mp // bm)
    o_bytes = mp * np_ * 4.0
    flops = 2.0 * mp * np_ * kp
    return max((a_bytes + b_bytes + o_bytes) / _HBM_BW, flops / _PEAK_INT8) \
        + steps * _STEP_OVERHEAD_S


def _measure_time_s(kind: str, m: int, n: int, k: int,
                    block: Tuple[int, int, int], *, fused: bool,
                    a_in_bytes: int = 2, reps: int = 3) -> float:
    """Median wall-clock of the real kernel on synthetic operands (TPU path)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.camp_gemm import camp_gemm_i8
    from repro.kernels.camp_gemm_fused import (camp_gemm_fused_w4a4,
                                               camp_gemm_fused_w4a8,
                                               camp_gemm_fused_w8a8)
    from repro.kernels.camp_gemm_w4 import camp_gemm_a4w4, camp_gemm_w4

    bm, bn, bk = block
    sb = jnp.ones((1, n), jnp.float32)
    kw = dict(block_m=bm, block_n=bn, block_k=bk)
    if fused:
        x = jnp.zeros((m, k), jnp.bfloat16 if a_in_bytes == 2 else jnp.float32)
        bq = jnp.zeros(((k if kind == "i8" else k // 2), n), jnp.int8)
        fn = {"i8": camp_gemm_fused_w8a8, "w4": camp_gemm_fused_w4a8,
              "a4w4": camp_gemm_fused_w4a4}[kind]
        call = lambda: fn(x, bq, sb, **kw)  # noqa: E731
    else:
        sa = jnp.ones((m, 1), jnp.float32)
        if kind == "i8":
            a = jnp.zeros((m, k), jnp.int8)
            bq = jnp.zeros((k, n), jnp.int8)
            call = lambda: camp_gemm_i8(a, bq, sa, sb, **kw)  # noqa: E731
        elif kind == "w4":
            a = jnp.zeros((m, k), jnp.int8)
            bq = jnp.zeros((k // 2, n), jnp.int8)
            call = lambda: camp_gemm_w4(a, bq, sa, sb, **kw)  # noqa: E731
        else:
            a = jnp.zeros((m, k // 2), jnp.int8)
            bq = jnp.zeros((k // 2, n), jnp.int8)
            call = lambda: camp_gemm_a4w4(a, bq, sa, sb, **kw)  # noqa: E731
    jax.block_until_ready(call())  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def flush() -> None:
    """Write the in-memory cache through to disk (for ``save=False`` loops)."""
    with _lock:
        _save_disk()


def has_cached(kind: str, m: int, n: int, k: int, *, fused: bool = False,
               a_in_bytes: int = 4) -> bool:
    """Is (kind, fused, m, n, k) already tuned for this backend?

    Lets warmup loops skip shapes a previous process (or an earlier
    enumeration pass in the same warmup) already paid for, instead of
    re-tuning — :func:`tune` itself always re-scores.
    """
    key = _key(kind, fused, m, n, k, _backend(), a_in_bytes)
    with _lock:
        _load_disk()
        return key in _mem_cache


def tune(kind: str, m: int, n: int, k: int, *, fused: bool = False,
         a_in_bytes: int = 4, measure: Optional[bool] = None,
         timer: Optional[Callable] = None,
         save: bool = True) -> Tuple[int, int, int]:
    """Pick the best block for (kind, fused, m, n, k) and cache it.

    ``measure=None`` → measure iff running on a real TPU backend. ``timer``
    overrides the per-candidate scorer (tests use this). ``save=False``
    defers the disk write — callers tuning many shapes in a loop should
    :func:`flush` once at the end instead of rewriting the JSON per shape.
    """
    if kind not in KINDS:
        raise ValueError(f"kind={kind!r} not in {KINDS}")
    backend = _backend()
    if measure is None:
        measure = backend == "tpu"
    key = _key(kind, fused, m, n, k, backend, a_in_bytes)

    best, best_t, source = None, float("inf"), "model"
    cands = candidates(kind, m, n, k, fused=fused, a_in_bytes=a_in_bytes)
    for blk in cands:
        src = "model"
        if timer is not None:
            t = timer(blk)
        elif measure:
            try:
                t = _measure_time_s(kind, m, n, k, blk, fused=fused,
                                    a_in_bytes=a_in_bytes)
                src = "measured"
            except Exception:
                # Mosaic rejected this candidate — never let it compete (an
                # analytic score would beat every *measured* wall-clock and a
                # non-compiling block would get cached).
                continue
        else:
            t = model_time_s(kind, m, n, k, blk, fused=fused,
                             a_in_bytes=a_in_bytes)
        if t < best_t:
            best, best_t, source = blk, t, src
    if best is None:
        # Every candidate failed to compile — pick the analytic best so the
        # caller's error is the kernel's own (reproducible) compile error.
        best = min(cands, key=lambda b: model_time_s(
            kind, m, n, k, b, fused=fused, a_in_bytes=a_in_bytes))
        best_t = model_time_s(kind, m, n, k, best, fused=fused,
                              a_in_bytes=a_in_bytes)

    with _lock:
        _load_disk()
        _mem_cache[key] = {"block": list(best), "source": source,
                           "t_us": best_t * 1e6}
        if save:
            _save_disk()
    return best


# ---------------------------------------------------------------------------
# Paged-attention page-size tuning (same persistent cache, its own key space)
# ---------------------------------------------------------------------------
PAGE_SIZES = (8, 16, 32, 64, 128)


def model_paged_decode_time_s(batch: int, kv_heads: int, head_dim: int,
                              mean_len: int, page_size: int) -> float:
    """Analytic v5e time for one layer's paged int8 decode-attention step.

    HBM term: each sequence streams its occupied pages (k+v int8 +
    per-token f32 scales); the expected half-empty last page charges
    fragmentation to large pages. Overhead term: one grid step per
    (seq, kv head, page) charges the per-step issue cost to small pages.
    """
    pages = mean_len / page_size + 0.5
    page_bytes = 2 * page_size * (head_dim + 4)   # int8 k+v + per-token scales
    hbm = batch * kv_heads * pages * page_bytes
    steps = batch * kv_heads * math.ceil(mean_len / page_size + 0.5)
    return hbm / _HBM_BW + steps * _STEP_OVERHEAD_S


def get_page_size(kv_heads: int, head_dim: int, mean_len: int,
                  batch: int = 8, *, timer: Optional[Callable] = None,
                  save: bool = True) -> int:
    """Cached KV page-size pick for a serving shape; tunes on first sight.

    Lives in the same JSON cache as the GEMM blocks (its own ``pattn|`` key
    space), so a pool size tuned by one serving process is reused by the
    next. ``timer`` overrides the analytic scorer (tests use this).
    """
    key = (f"pattn|kv{kv_heads}|hd{head_dim}|len{mean_len}|b{batch}"
           f"|{_backend()}")
    with _lock:
        _load_disk()
        hit = _mem_cache.get(key)
    if hit is not None:
        return int(hit["page_size"])
    score = timer or (lambda ps: model_paged_decode_time_s(
        batch, kv_heads, head_dim, mean_len, ps))
    scores = {ps: score(ps) for ps in PAGE_SIZES}
    best = min(scores, key=scores.get)
    with _lock:
        _load_disk()
        _mem_cache[key] = {"page_size": int(best),
                           "source": "timer" if timer else "model",
                           "t_us": scores[best] * 1e6}
        if save:
            _save_disk()
    return int(best)


# ---------------------------------------------------------------------------
# Chunked paged-prefill tuning (same persistent cache, ``pprefill|`` keys)
# ---------------------------------------------------------------------------
PREFILL_CHUNKS = (64, 128, 256, 512)
PREFILL_PAGES_PER_STEP = (1, 2, 4, 8)


def model_paged_prefill_time_s(kv_heads: int, head_dim: int, page_size: int,
                               mean_len: int, chunk: int,
                               pages_per_step: int, group: int = 1) -> float:
    """Analytic v5e per-token time of one layer's chunked paged prefill.

    Each chunk re-streams the sequence's cached pages once (k+v int8 +
    per-token scales), so bigger chunks amortize the restream; one grid step
    covers ``pages_per_step`` pages, so bigger steps amortize issue
    overhead. The kernel folds the ``group`` query heads of a kv head into
    its rows, and what it keeps per row must fit the scoped VMEM limit,
    which bounds both from above: about 32 bytes per head-dim element (the
    f32 accumulator and q copy, the double-buffered q and output blocks,
    the lane-padded running max and sum) and 8 per kv-block token (the f32
    scores and probabilities). The v5e compiler asks for 17.2 MB at 4096
    rows, hd 128 and a 32-token block, and 25.3 MB at 3584 rows, hd 64 and
    a 1024-token block; 16 MB is the limit.
    """
    n_pages = mean_len / page_size + 0.5
    page_bytes = 2 * page_size * (head_dim + 4)   # int8 k+v + per-token scales
    hbm = kv_heads * n_pages * page_bytes + chunk * 2 * kv_heads * head_dim * 2
    steps = kv_heads * math.ceil(n_pages / pages_per_step)
    rows = chunk * group
    per_row = 32 * head_dim + 8 * pages_per_step * page_size
    if rows * per_row > 0.9 * VMEM_BYTES:
        return float("inf")
    return (hbm / _HBM_BW + steps * _STEP_OVERHEAD_S) / chunk


def get_prefill_params(kv_heads: int, head_dim: int, page_size: int,
                       mean_len: int, *, group: int = 1,
                       timer: Optional[Callable] = None,
                       save: bool = True) -> Tuple[int, int]:
    """Cached (chunk_tokens, pages_per_step) pick for chunked paged prefill.

    ``group``: query heads per kv head. Lives in the same JSON cache as the
    GEMM blocks (its own ``pprefill|`` key space). ``timer(chunk,
    pages_per_step)`` overrides the analytic scorer (tests use this).
    """
    key = (f"pprefill|kv{kv_heads}|g{group}|hd{head_dim}|ps{page_size}"
           f"|len{mean_len}|{_backend()}")
    with _lock:
        _load_disk()
        hit = _mem_cache.get(key)
    if hit is not None:
        return int(hit["chunk"]), int(hit["pages_per_step"])
    score = timer or (lambda c, pp: model_paged_prefill_time_s(
        kv_heads, head_dim, page_size, mean_len, c, pp, group))
    scores = {(c, pp): score(c, pp)
              for c in PREFILL_CHUNKS for pp in PREFILL_PAGES_PER_STEP}
    best = min(scores, key=scores.get)
    with _lock:
        _load_disk()
        _mem_cache[key] = {"chunk": int(best[0]),
                           "pages_per_step": int(best[1]),
                           "source": "timer" if timer else "model",
                           "t_us": scores[best] * 1e6}
        if save:
            _save_disk()
    return int(best[0]), int(best[1])


# ---------------------------------------------------------------------------
# Speculative-decoding window tuning (same persistent cache, ``spec|`` keys)
# ---------------------------------------------------------------------------
SPEC_GAMMAS = (1, 2, 3, 4, 6, 8)
DEFAULT_SPEC_GAMMA = 4
# Marginal cost of one extra verify row relative to a whole decode step.
# Decode is memory-bound: the weight/cache stream is paid once per forward
# whether it scores 1 row or γ+1, so extra rows cost only their (tiny)
# compute slice — the whole reason speculation pays.
_SPEC_ROW_COST = 0.06


def expected_spec_tokens(gamma: int, acceptance: float) -> float:
    """E[tokens emitted per verify step] under per-token acceptance rate
    ``acceptance``: 1 + a + a² + … + a^γ (the classic geometric series —
    the step always emits at least one token)."""
    a = min(max(acceptance, 0.0), 1.0)
    if a >= 1.0:
        return float(gamma + 1)
    return (1.0 - a ** (gamma + 1)) / (1.0 - a)


def get_spec_gamma(acceptance: float, *, draft_cost: float = 0.0,
                   timer: Optional[Callable] = None,
                   save: bool = True) -> int:
    """Cached speculation-window pick from measured acceptance × cost.

    Scores each candidate γ by expected tokens per unit cost, where one
    verify step costs ``1 + _SPEC_ROW_COST·γ + draft_cost·γ`` decode-step
    equivalents (``draft_cost``: the drafter's per-token cost ratio — 0 for
    n-gram lookup, ~0.25 for a small draft model). Acceptance is bucketed
    to 0.05 so the ``spec|`` key space stays bounded; ``timer(gamma)``
    overrides the analytic scorer (tests use this). Lives in the same JSON
    cache as the GEMM blocks, so a window tuned by one serving process is
    reused by the next.
    """
    bucket = round(min(max(acceptance, 0.0), 0.95) * 20) / 20
    key = f"spec|acc{bucket:.2f}|dc{draft_cost:.2f}|{_backend()}"
    with _lock:
        _load_disk()
        hit = _mem_cache.get(key)
    if hit is not None:
        return int(hit["gamma"])
    score = timer or (lambda g: -expected_spec_tokens(g, bucket)
                      / (1.0 + _SPEC_ROW_COST * g + draft_cost * g))
    scores = {g: score(g) for g in SPEC_GAMMAS}
    best = min(scores, key=scores.get)
    with _lock:
        _load_disk()
        _mem_cache[key] = {"gamma": int(best),
                           "source": "timer" if timer else "model",
                           "score": scores[best]}
        if save:
            _save_disk()
    return int(best)


def get_blocks(kind: str, m: int, n: int, k: int, *, fused: bool = False,
               a_in_bytes: int = 4,
               allow_measure: bool = False) -> Tuple[int, int, int]:
    """Cached block lookup; tunes (and persists) on first sight of a shape.

    ``allow_measure=False`` keeps cold-cache lookups cheap and trace-safe:
    analytic pick now, and a serving warmup (:func:`tune` with measurement)
    can overwrite the entry later.
    """
    backend = _backend()
    key = _key(kind, fused, m, n, k, backend, a_in_bytes)
    with _lock:
        _load_disk()
        hit = _mem_cache.get(key)
    if hit is not None:
        return tuple(hit["block"])
    return tune(kind, m, n, k, fused=fused, a_in_bytes=a_in_bytes,
                measure=(None if allow_measure else False))
