"""Serving engine: continuous batching over a paged, quantized KV cache.

This is where the CAMP technique earns its keep at scale: decode is
memory-roofline-bound, so int8/int4 weights (``cfg.qmode``) cut the weight
stream and the paged int8 KV cache (:mod:`repro.serving.kv_cache`) cuts the
cache stream — decode reads only the pages a sequence occupies, at one byte
per element, dequantized in-register by the paged-attention kernel.

The paged pool is the **only** KV representation end to end: prefill is
**chunked** and writes quantized pages directly through
:class:`~repro.serving.kv_cache.PagedPrefillCache` (no dense per-request KV
staging slab exists anywhere in the engine), decode appends to the same
pages, and prompts sharing a prefix share physical pages copy-on-write.

Two serving modes:

* :class:`ContinuousBatchingEngine` — sequences are admitted and finished
  **mid-flight** over a shared page pool: ``submit()`` queues a request;
  every ``step()`` admits what fits (reserving only the pages a prefix
  lookup could not share), advances the head prefill by one autotuned
  chunk, and runs one ragged decode over all active sequences (per-sequence
  positions and block tables; no padding to a common length) — so a long
  prompt no longer stalls decode for the whole batch, and a long request no
  longer holds the batch hostage. Finished sequences decref their pages;
  slots return to the free list when the last sharer is done.
  ``generate()`` is a thin batch wrapper on top.
* the dense-slab path (``build_prefill_step`` / ``build_decode_step``) —
  the degenerate single-block-table case, kept for hybrid/recurrent mixers
  (SSM/RWKV carry non-KV state) and for the multi-pod dry-run cells.

Both engine modes are **mesh-native**: pass ``mesh=`` and every forward
runs under the serve-mode sharding rules — KV pages head-sharded over the
model axis, shard_map attention kernels, row-parallel output projections
with (optionally int8-compressed) all-reduces — while the scheduler itself
remains ordinary replicated host code.

The continuous engine also speaks **speculative decoding** (``spec=``):
the decode lane swaps single-token steps for draft–verify panels — a
drafter (:mod:`repro.serving.spec_decode`) proposes γ tokens, one
γ+1-token forward over the paged cache scores them, exact
acceptance–rejection keeps the agreed prefix and the pool's token-granular
``truncate`` rolls the rest back. Greedy speculative streams are
bit-identical to non-speculative ones; temperature streams preserve the
target distribution.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.models.config import ModelConfig
from repro.models.moe import expert_capacity, routing_group_size
from repro.models.transformer import forward, init_caches
from repro.parallel.sharding import (effective_model_shards, make_rules,
                                     mesh_context)
from repro.serving import kv_cache as kvc
from repro.serving import spec_decode as sd
from repro.serving import tracing


def init_serve_caches(cfg: ModelConfig, batch: int, max_len: int,
                      kv_dtype: Optional[str] = None):
    """Dense KV/state caches; ``kv_dtype='int8'`` stores attention KV
    quantized with **per-page dynamic scales** (amax/127 of each page —
    scale handling lives in :mod:`repro.serving.kv_cache`, shared with the
    paged pool)."""
    return init_caches(cfg, batch, max_len, kv_dtype=kv_dtype)


_QMODE_KIND = {"w8a8": "i8", "w4a8": "w4", "w4a4": "a4w4"}


def warm_gemm_autotune(cfg: ModelConfig, *, batch_sizes=(1, 8, 32),
                       prefill_len: int = 0, measure=None, tp: int = 1,
                       spec_gammas=()):
    """Pre-tune CAMP GEMM blocks for the transformer's serving linears.

    Decode runs one token per sequence (M = batch) and prefill runs
    M = batch × prompt_len; both hit the same (K, N) weight shapes. Tuning
    them here — measured on a live TPU, analytic elsewhere — populates the
    persistent autotune cache so the request path never tunes. Covered:
    attention q/kv/out, dense MLP up/gate/down, MoE expert up/gate/down at
    the **expert-capacity M** the per-expert fused CAMP dispatch in
    :mod:`repro.models.moe` actually runs, and the untied lm head.
    Mixer-specific extras (SSM/RWKV projections) still cold-tune on first
    sight.

    ``tp > 1`` warms the tensor-parallel shard shapes instead: column-
    parallel projections run (m, n/tp, k) per device and the row-parallel
    wo/w_down run (m, n, k/tp) — the shapes the shard_map call paths
    actually launch. The enumeration is a set and shapes already present in
    the persistent cache are skipped, so serve-mode warming (which visits
    both the sharded and the replicated-fallback shapes across engine
    restarts) never tunes the same (M, N, K) twice.

    ``spec_gammas`` adds the speculative-decoding verify panels: a γ-token
    draft is verified by one (γ+1)-row forward per sequence, and drafters
    routinely propose *fewer* than γ tokens (no n-gram match, short
    continuations, end-of-budget clipping), so every partial panel width
    M ∈ [2, γ+1] joins the enumeration for each candidate window.

    Returns [((m, n, k), (bm, bn, bk)), ...] for logging.
    """
    kind = _QMODE_KIND.get(cfg.qmode)
    if kind is None:  # 'none' / weight-only: bf16 matmul, nothing to tune
        return []
    a_in_bytes = jnp.dtype(cfg.dtype).itemsize  # must match the request path
    d, hd = cfg.d_model, cfg.hd

    def shard(k, n, *, row_parallel):
        """Local (K, N) of one device's GEMM under tp-way model sharding."""
        if tp <= 1:
            return (k, n)
        if row_parallel:
            return (k // tp, n) if k % tp == 0 else (k, n)
        return (k, n // tp) if n % tp == 0 else (k, n)

    proj = {
        shard(d, hd * cfg.n_heads, row_parallel=False),    # q proj
        shard(d, hd * cfg.n_kv_heads, row_parallel=False),  # kv proj
        shard(hd * cfg.n_heads, d, row_parallel=True),     # attn out
        shard(d, cfg.d_ff, row_parallel=False),            # mlp up/gate
        shard(cfg.d_ff, d, row_parallel=True),             # mlp down
    }
    if not cfg.tie_embeddings:
        proj.add(shard(d, cfg.vocab_size, row_parallel=False))  # lm head
    ms = sorted({b * max(prefill_len, 1) for b in batch_sizes} |
                set(batch_sizes) |
                {m for g in spec_gammas for m in range(2, g + 2)})
    shapes = {(m, n, k) for m in ms for (k, n) in proj}
    if cfg.moe_experts:
        # expert GEMMs run at M = groups × capacity, not M = tokens
        eproj = (shard(d, cfg.expert_ff, row_parallel=False),
                 shard(cfg.expert_ff, d, row_parallel=True))
        for m in ms:
            sg = routing_group_size(m)
            em = (m // sg) * expert_capacity(sg, cfg)
            shapes |= {(max(em, 1), n, k) for (k, n) in eproj}
    out = []
    for (m, n, k) in sorted(shapes):
        if autotune.has_cached(kind, m, n, k, fused=True,
                               a_in_bytes=a_in_bytes):
            continue           # a previous warmup already paid for this one
        blk = autotune.tune(kind, m, n, k, fused=True,
                            a_in_bytes=a_in_bytes, measure=measure,
                            save=False)
        out.append(((m, n, k), blk))
    autotune.flush()  # one disk write for the whole warmup
    return out


def paged_shape(cfg: ModelConfig, page_size: Optional[int] = None):
    """(page size, prefill chunk, pages per prefill grid step) the engine
    serves ``cfg`` with: from the persistent autotune cache (the analytic
    v5e model off-TPU), unless the caller pins the page size."""
    mean_len = max(cfg.max_seq_len // 2, 128)
    ps = page_size or autotune.get_page_size(cfg.n_kv_heads, cfg.hd,
                                             mean_len=mean_len)
    chunk, pp = autotune.get_prefill_params(
        cfg.n_kv_heads, cfg.hd, ps, mean_len=mean_len,
        group=cfg.n_heads // cfg.n_kv_heads)
    return ps, chunk, pp


def build_prefill_step(cfg: ModelConfig, *, max_len: Optional[int] = None):
    """(params, inputs, caches) → (last_token_logits, caches)."""

    def prefill_step(params, inputs, caches):
        # last_logits_only: a 32k prefill needs the head at ONE position,
        # not a (B, 32768, V) logits tensor.
        logits, caches, _ = forward(params, cfg, inputs, caches=caches,
                                    last_logits_only=True)
        return logits[:, -1], caches

    return prefill_step


def build_decode_step(cfg: ModelConfig, *, sample: str = "greedy",
                      temperature: float = 1.0):
    """(params, caches, token, pos, key) → (next_token, caches).

    ``token``: (B, 1) int32; ``pos``: scalar int32 current position.
    """

    def decode_step(params, caches, token, pos, key=None):
        logits, caches, _ = forward(params, cfg, token, caches=caches,
                                    cache_pos=pos)
        last = logits[:, -1].astype(jnp.float32)
        if sample == "greedy":
            nxt = jnp.argmax(last, axis=-1)
        else:
            nxt = jax.random.categorical(key, last / temperature, axis=-1)
        return nxt[:, None].astype(jnp.int32), caches

    return decode_step


# ---------------------------------------------------------------------------
# Continuous batching over the shared page pool
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    """One in-flight generation request."""
    seq_id: int
    prompt: jax.Array                    # (S,) int32
    max_new_tokens: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    pos: int = 0                         # prompt tokens cached so far
    done: bool = False
    spec: sd.SpecStats = dataclasses.field(default_factory=sd.SpecStats)

    def __post_init__(self):
        # host-side token tuple: prefix-trie keys + chunk slicing without
        # device round-trips per step
        self.prompt_tokens = tuple(np.asarray(self.prompt).tolist())

    @property
    def reserve_tokens(self) -> int:
        return int(self.prompt.shape[0]) + self.max_new_tokens


class ContinuousBatchingEngine:
    """Admit/finish sequences mid-flight over a shared paged KV pool.

    Scheduling is conservative: a request is admitted only when the pool can
    reserve its worst-case page count (prompt + max_new_tokens, minus the
    prefix pages a trie lookup can share), so an admitted sequence can never
    stall mid-decode waiting for pages. Each ``step()``:

    1. admits the next queued request once the prefill lane is clear —
       admission looks up the prompt in the pool's prefix trie, **shares**
       the pages of any registered prefix (refcounted, copy-on-write) and
       reserves only the remainder; admitting one prefill at a time lets a
       burst of same-prefix prompts share the pages the first one writes;
    2. advances the head prefill by one autotuned **chunk** (``pprefill|``
       autotune keys): the chunk's KV quantizes straight into the
       sequence's pages and attends over the cached prefix through the
       chunked paged-prefill kernel — no dense per-request KV slab exists,
       and a 32k prompt no longer blocks the batch for its whole prefill;
    3. runs **one ragged decode** over every active sequence: per-sequence
       positions, per-sequence block tables, one forward pass — attention
       goes through the paged int8 kernel, so a step's HBM traffic is the
       pages actually occupied, not ``batch × max_len``;
    4. retires sequences that hit their token budget and decrefs their
       pages — a slot returns to the free list when its last sharer is done.

    Per-sequence results are independent of co-scheduling: pages are owned
    exclusively or shared immutably (every write path crosses the pool's
    copy-on-write barrier), per-token scales depend only on a token's own
    content, attention is masked per sequence length, chunk boundaries
    depend only on the engine's static chunk size, and sampling keys are
    derived per (seq_id, token index) — a sequence decodes identically
    whether it runs alone or inside a changing batch.

    **Tensor parallelism.** With ``mesh=`` (a (data, model) device mesh),
    every forward runs inside a ``mode='serve'`` mesh context: the pool's
    page storage is head-sharded over the model axis, the paged kernels run
    their shard_map wrappers (KV hot path collective-free), and the
    row-parallel wo/w_down projections all-reduce their partial outputs —
    int8-compressed on the wire when ``tp_int8_reduce``. Scheduler state
    (queues, block tables, trie, refcounts) stays replicated host-side, so
    admission/retirement logic is identical with and without a mesh; a
    kv-head count indivisible by the model axis degrades to replicated
    attention and the engine behaves exactly as on a single device.

    **Speculative decoding.** With ``spec=``
    (:class:`repro.serving.spec_decode.SpecConfig`, method 'ngram' or
    'draft'), the decode lane runs **draft–verify** steps instead of
    single-token ragged decodes: per active sequence, the drafter proposes
    up to γ tokens, their KV is written into the sequence's pages (crossing
    the COW barrier page by page) and the whole γ+1-token panel is scored
    by ONE forward through the chunked paged-prefill path — then exact
    acceptance–rejection keeps the agreed prefix and
    :meth:`~repro.serving.kv_cache.PagePool.truncate` rolls the rejected
    suffix back. Write-once token-granular pages make the rollback
    bit-exact, so greedy speculative streams are identical to
    non-speculative ones and temperature streams preserve the target
    distribution for any drafter. Speculation targets small-batch,
    latency-bound serving (the verify forwards run per sequence);
    mid-prefill requests keep the normal chunked path, and hybrid
    SSM/RWKV models never reach this engine at all. Drafting always runs
    replicated (outside the mesh scope); only verification is
    tensor-parallel. ``gamma='auto'`` re-picks the window from the
    measured acceptance rate through the autotune cache's ``spec|`` keys.

    **Tracing.** ``tracer=`` (:class:`repro.serving.tracing.Tracer`;
    default :data:`repro.serving.tracing.DEFAULT`) gets a span at each
    host-side phase of a step: ``serve.step``, ``serve.admit`` per admitted
    request, ``serve.prefill_chunk``, ``serve.decode``, and inside the two
    lanes ``serve.forward`` (the forward and its writeback) and
    ``serve.sample`` (where the host waits for the sampled tokens). The
    speculative lane has none yet.
    """

    SPEC_RETUNE_EVERY = 16               # spec steps between auto-γ re-picks

    def __init__(self, params, cfg: ModelConfig, *,
                 kv_dtype: Optional[str] = "int8",
                 page_size: Optional[int] = None,
                 capacity_tokens: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 pages_per_step: Optional[int] = None,
                 sample: str = "greedy", temperature: float = 1.0,
                 key: Optional[jax.Array] = None,
                 mesh=None, rules=None, tp_int8_reduce: bool = False,
                 retain_pages: Optional[int] = None,
                 spec: Optional[sd.SpecConfig] = None,
                 tracer: Optional[tracing.Tracer] = None):
        mixers = {cfg.mixer_of(i) for i in range(cfg.n_layers)}
        if mixers != {"attn"}:
            raise ValueError(
                f"continuous batching requires attention mixers, got {mixers}"
                " (hybrid/recurrent models use the dense serving path)")
        self.params, self.cfg = params, cfg
        self.tracer = tracer if tracer is not None else tracing.DEFAULT
        self.sample, self.temperature = sample, temperature
        self.key = jax.random.PRNGKey(0) if key is None else key
        self.mesh = mesh
        self.rules = rules if rules is not None else (
            make_rules("serve") if mesh is not None else None)
        self.tp_int8_reduce = tp_int8_reduce
        # sharding degree the pool/kernels actually get (replicated fallback
        # for head counts the model axis doesn't divide)
        self.tp = effective_model_shards(mesh, cfg.n_kv_heads)
        ps, chunk, pp = paged_shape(cfg, page_size)
        chunk = prefill_chunk or chunk
        # non-final chunks must cover whole pages so a partial page is
        # quantized exactly once (by the final chunk)
        self.chunk_tokens = max(ps, chunk - chunk % ps)
        self.pages_per_step = pages_per_step or pp
        capacity_tokens = capacity_tokens or 8 * cfg.max_seq_len
        self.pool = kvc.PagePool(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            num_pages=-(-capacity_tokens // ps), page_size=ps,
            quantized=(kv_dtype == "int8"), dtype=jnp.dtype(cfg.dtype),
            mesh=mesh if self.tp > 1 else None, retain_pages=retain_pages)
        self.waiting: collections.deque = collections.deque()
        self.prefilling: collections.deque = collections.deque()
        self.active: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self._next_id = 0
        # -- speculative decoding ---------------------------------------
        self.spec_cfg = spec if spec is not None and spec.method != "off" \
            else None
        self.drafter = None
        self.spec_totals = sd.SpecStats()
        if self.spec_cfg is not None:
            self.drafter = sd.make_drafter(
                self.spec_cfg, sample=sample, temperature=temperature,
                key=jax.random.fold_in(self.key, 0x5bec))
            self._spec_auto = self.spec_cfg.gamma == "auto"
            self._spec_last_tune = 0
            self.spec_gamma = (autotune.DEFAULT_SPEC_GAMMA if self._spec_auto
                               else int(self.spec_cfg.gamma))
            if self.spec_gamma < 1:
                raise ValueError(f"spec gamma {self.spec_gamma} < 1")

    def _mesh_scope(self):
        """Serve-mode mesh context for one engine step (no-op without mesh)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return mesh_context(self.mesh, self.rules, mode="serve",
                            opts={"tp_int8_reduce": self.tp_int8_reduce})

    # -- request lifecycle ----------------------------------------------
    def submit(self, prompt, max_new_tokens: int) -> int:
        """Queue a prompt; returns its sequence id."""
        prompt = jnp.asarray(prompt, jnp.int32).reshape(-1)
        seq_id = self._next_id
        self._next_id += 1
        self.waiting.append(Request(seq_id, prompt, max_new_tokens))
        return seq_id

    def _sample_tokens(self, logits: jax.Array,
                       reqs: List[Request]) -> jax.Array:
        """logits (B, V) → (B,) int32; rows align with ``reqs``.

        Non-greedy keys are folded from (engine key, seq_id, token index),
        never from a shared stream — so sampled tokens don't depend on which
        other sequences happen to share the batch.
        """
        last = logits.astype(jnp.float32)
        if self.sample == "greedy":
            return jnp.argmax(last, axis=-1)
        keys = jnp.stack([
            jax.random.fold_in(jax.random.fold_in(self.key, r.seq_id),
                               len(r.tokens))
            for r in reqs])
        return jax.vmap(
            lambda k, l: jax.random.categorical(k, l / self.temperature)
        )(keys, last)

    def _finish(self, req: Request) -> None:
        self.pool.release(req.seq_id)
        if self.drafter is not None:
            self.drafter.release(req.seq_id)
        req.done = True
        self.finished[req.seq_id] = req

    def _admit(self) -> None:
        """Admit the next queued request once the prefill lane is clear.

        One prefill in flight at a time: by the time the next request is
        admitted, the previous prompt's full pages are registered in the
        prefix trie, so a burst of same-prefix prompts shares pages instead
        of each writing its own copy. Admission reserves only the pages the
        prefix lookup could not share.
        """
        while self.waiting and not self.prefilling:
            nxt: Request = self.waiting[0]
            if not self.pool.can_reserve(nxt.reserve_tokens,
                                         prompt=nxt.prompt_tokens):
                if not self.active:
                    raise RuntimeError(
                        f"request {nxt.seq_id} needs "
                        f"{self.pool.pages_for(nxt.reserve_tokens)} pages; "
                        f"pool has {self.pool.num_pages} total")
                break
            self.waiting.popleft()
            with self.tracer.span("serve.admit", seq_id=nxt.seq_id,
                                  prompt_tokens=len(nxt.prompt_tokens)):
                nxt.pos = self.pool.reserve(nxt.seq_id, nxt.reserve_tokens,
                                            prompt=nxt.prompt_tokens)
                self.tracer.note(matched=nxt.pos)
            self.prefilling.append(nxt)

    def _run_prefill_chunk(self, req: Request, chunk: int,
                           need_logits: bool):
        """One chunk of paged prefill: tokens [pos, pos+chunk) straight into
        the pool's pages (no dense staging slab). Mid-prompt chunks skip
        the vocabulary head entirely."""
        t0 = req.pos
        with self.tracer.span("serve.forward"):
            logits = sd.paged_chunk_forward(
                self.params, self.cfg, self.pool, req.seq_id,
                req.prompt[t0:t0 + chunk], t0,
                pages_per_step=self.pages_per_step,
                logits="last" if need_logits else "none")
        req.pos = t0 + chunk
        return logits

    def _prefill_step(self) -> None:
        """Advance the head prefill by up to ``chunk_tokens`` prompt tokens.

        Non-final chunks are page-aligned, so every page is quantized
        exactly once; the final chunk registers the prompt's full pages in
        the prefix trie and moves the request to the decode lane.
        """
        budget = self.chunk_tokens
        while budget > 0 and self.prefilling:
            req: Request = self.prefilling[0]
            s = int(req.prompt.shape[0])
            remaining = s - req.pos
            chunk = min(budget, remaining)
            if chunk < remaining:
                chunk -= chunk % self.pool.page_size
                if chunk == 0:
                    break        # leftover budget smaller than one page
            last = req.pos + chunk == s
            with self.tracer.span("serve.prefill_chunk", seq_id=req.seq_id,
                                  q_start=req.pos, tokens=chunk, last=last):
                logits = self._run_prefill_chunk(req, chunk, need_logits=last)
                if last:
                    self.prefilling.popleft()
                    self.pool.register_prefix(req.seq_id, req.prompt_tokens)
                    with self.tracer.span("serve.sample", rows=1):
                        req.tokens.append(int(self._sample_tokens(
                            logits[:, -1], [req])[0]))
            budget -= chunk
            if not last:
                continue
            if len(req.tokens) >= req.max_new_tokens:
                self._finish(req)
            else:
                self.active.append(req)

    def _decode(self) -> None:
        """One ragged decode step over all active sequences."""
        reqs = list(self.active)
        seq_ids = [r.seq_id for r in reqs]
        ps = self.pool.page_size
        with self.tracer.span("serve.decode", rows=len(reqs),
                              seq_ids=seq_ids):
            for r in reqs:
                # COW barrier: the page this append touches must be exclusive
                self.pool.ensure_writable(r.seq_id,
                                          self.pool.lens[r.seq_id] // ps)
            tokens = jnp.asarray([[r.tokens[-1]] for r in reqs], jnp.int32)
            tables, lengths = self.pool.batch_tables(seq_ids)
            self.tracer.note(max_pages=int(tables.shape[1]))
            caches = [{"attn": self.pool.layer_cache(i, tables, lengths)}
                      for i in range(self.cfg.n_layers)]
            with self.tracer.span("serve.forward"):
                logits, new_caches, _ = forward(self.params, self.cfg, tokens,
                                                positions=lengths[:, None],
                                                caches=caches)
                for i, layer in enumerate(new_caches):
                    self.pool.writeback(i, layer["attn"])
            for r in reqs:
                self.pool.lens[r.seq_id] += 1
            with self.tracer.span("serve.sample", rows=len(reqs)):
                nxt = np.asarray(self._sample_tokens(logits[:, -1], reqs))
        self.active = []
        for r, t in zip(reqs, nxt):
            r.tokens.append(int(t))
            if len(r.tokens) >= r.max_new_tokens:
                self._finish(r)
            else:
                self.active.append(r)

    # -- speculative decode lane -----------------------------------------
    def _spec_verify(self, req: Request, draft: List[int]) -> np.ndarray:
        """Score [last_sampled] + draft in one forward over the paged cache.

        The panel's KV is written into the sequence's pages first (each
        touched page crosses the COW barrier), then the γ+1-token query
        attends over the whole cached prefix through the chunked
        paged-prefill path — ``q_start`` is wherever decode left off,
        page-aligned or not. Returns the (γ+1, V) f32 logit rows; the
        caller rolls the rejected suffix back with ``pool.truncate``.
        """
        L = self.pool.lens[req.seq_id]
        m = 1 + len(draft)
        ps = self.pool.page_size
        for pidx in range(L // ps, (L + m - 1) // ps + 1):
            self.pool.ensure_writable(req.seq_id, pidx)
        with self._mesh_scope():
            logits = sd.paged_chunk_forward(
                self.params, self.cfg, self.pool, req.seq_id,
                [req.tokens[-1]] + draft, L,
                pages_per_step=self.pages_per_step, logits="all")
        return np.asarray(logits[0], np.float32)

    def _spec_one(self, req: Request) -> None:
        """One draft–verify–rollback step for one active sequence."""
        remaining = req.max_new_tokens - len(req.tokens)
        gamma = min(self.spec_gamma, remaining - 1)
        draft, draft_q = ([], None)
        if gamma > 0:
            # drafting always runs replicated (the verify forward below is
            # the only mesh-parallel part of a speculative step); the draft
            # reservation covers the largest window auto-tuning could pick
            gamma_cap = max(self.spec_gamma, max(autotune.SPEC_GAMMAS))
            draft, draft_q = self.drafter.propose(
                req.seq_id, list(req.prompt_tokens) + req.tokens, gamma,
                reserve_tokens=req.reserve_tokens + gamma_cap + 1)
        L = self.pool.lens[req.seq_id]
        rows = self._spec_verify(req, draft)
        n_acc, emitted = sd.accept_speculative(
            rows, draft, draft_q, sample=self.sample,
            temperature=self.temperature, key=self.key, seq_id=req.seq_id,
            start_index=len(req.tokens))
        # the cache must hold everything but the last emitted token
        self.pool.truncate(req.seq_id, L + n_acc + 1)
        req.tokens.extend(emitted)
        req.spec.add(len(draft), n_acc, len(emitted))
        self.spec_totals.add(len(draft), n_acc, len(emitted))
        if len(req.tokens) >= req.max_new_tokens:
            self._finish(req)
        else:
            self.active.append(req)

    def _spec_step(self) -> None:
        """Draft–verify every active sequence (replaces the ragged decode)."""
        reqs = list(self.active)
        self.active = []
        for r in reqs:
            self._spec_one(r)
        if (self._spec_auto and self.spec_totals.steps
                - self._spec_last_tune >= self.SPEC_RETUNE_EVERY):
            self._spec_last_tune = self.spec_totals.steps
            self.spec_gamma = autotune.get_spec_gamma(
                self.spec_totals.acceptance_rate,
                draft_cost=self.drafter.cost_ratio)

    def prefill_logits(self, tokens) -> np.ndarray:
        """(V,) f32 last-position logits of ``tokens`` prefilled through the
        engine's own chunked paged path (same chunks, pool, kernels and
        mesh), without admitting a request; the pages are released after.
        The served path's first-step logits, for parity checks."""
        tokens = list(np.asarray(tokens).reshape(-1))
        seq_id = -1 - self._next_id      # never a submitted request's id
        self.pool.reserve(seq_id, len(tokens))
        pos = 0
        with self._mesh_scope():
            while pos < len(tokens):
                c = min(self.chunk_tokens, len(tokens) - pos)
                out = sd.paged_chunk_forward(
                    self.params, self.cfg, self.pool, seq_id,
                    tokens[pos:pos + c], pos,
                    pages_per_step=self.pages_per_step,
                    logits="last" if pos + c == len(tokens) else "none")
                pos += c
        self.pool.release(seq_id)
        return np.asarray(out[0, -1], np.float32)

    def spec_summary(self) -> Dict:
        """Aggregate + per-request draft/verify stats (finished AND
        in-flight requests, so mid-serve polling sees every sequence the
        aggregate counters cover)."""
        reqs = list(self.finished.values()) + self.active \
            + list(self.prefilling) + list(self.waiting)
        per = {r.seq_id: r.spec.summary()
               for r in sorted(reqs, key=lambda r: r.seq_id)}
        out = self.spec_totals.summary()
        out.update(enabled=self.drafter is not None,
                   gamma=self.spec_gamma if self.drafter is not None else 0,
                   per_request=per)
        return out

    # -- driving ---------------------------------------------------------
    def step(self) -> bool:
        """Admit what fits, one prefill chunk, one ragged decode step.

        Returns True while work remains. Prefill chunks and decode steps
        interleave 1:1 under the chunk token budget, so time-to-first-token
        for queued prompts and inter-token latency for running sequences
        both stay bounded regardless of prompt length.
        """
        with self.tracer.span("serve.step", waiting=len(self.waiting),
                              prefilling=len(self.prefilling),
                              active=len(self.active)):
            self._admit()
            if self.prefilling:
                with self._mesh_scope():
                    self._prefill_step()
            if self.active:
                if self.drafter is not None:
                    self._spec_step()    # wraps only the verify in the mesh
                else:
                    with self._mesh_scope():
                        self._decode()
        return bool(self.active or self.waiting or self.prefilling)

    def run(self) -> Dict[int, List[int]]:
        """Drain all queued/active requests; {seq_id: generated tokens}."""
        while self.step():
            pass
        return {sid: list(r.tokens) for sid, r in self.finished.items()}


# ---------------------------------------------------------------------------
# Batched generation entrypoints
# ---------------------------------------------------------------------------
def _generate_dense(params, cfg: ModelConfig, prompt: jax.Array, *,
                    steps: int, key, sample: str, temperature: float,
                    max_len: Optional[int], kv_dtype: Optional[str]):
    """Legacy dense-slab loop (hybrid/recurrent mixers carry non-KV state)."""
    b, s = prompt.shape[:2]
    max_len = max_len or (s + steps)
    caches = init_serve_caches(cfg, b, max_len, kv_dtype=kv_dtype)
    prefill = build_prefill_step(cfg)
    decode = build_decode_step(cfg, sample=sample, temperature=temperature)
    last, caches = prefill(params, prompt, caches)
    tok = jnp.argmax(last.astype(jnp.float32), axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    for i in range(steps - 1):
        k = None if key is None else jax.random.fold_in(key, i)
        tok, caches = decode(params, caches, tok, jnp.int32(s + i), k)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def generate(params, cfg: ModelConfig, prompt: jax.Array, *, steps: int,
             key=None, sample: str = "greedy", temperature: float = 1.0,
             max_len: Optional[int] = None, kv_dtype: Optional[str] = None,
             page_size: Optional[int] = None, mesh=None,
             tp_int8_reduce: bool = False,
             retain_pages: Optional[int] = None,
             spec: Optional[sd.SpecConfig] = None):
    """Batched generation: prompt (B, S) → (B, steps) new tokens.

    All-attention models run on the continuous-batching engine (paged pool;
    pages are int8 when ``kv_dtype='int8'``, else the model dtype). Models
    with SSM/RWKV mixers fall back to the dense-slab loop (``spec`` is
    ignored there — speculation needs the paged cache's rollback). ``mesh``
    turns on tensor-parallel serving and ``spec`` turns on speculative
    decoding (see :class:`ContinuousBatchingEngine`).
    """
    b, s = prompt.shape[:2]
    if (cfg.embedding_inputs
            or any(cfg.mixer_of(i) != "attn" for i in range(cfg.n_layers))):
        return _generate_dense(params, cfg, prompt, steps=steps, key=key,
                               sample=sample, temperature=temperature,
                               max_len=max_len, kv_dtype=kv_dtype)
    ps = page_size or kvc.DEFAULT_PAGE_SIZE
    eng = ContinuousBatchingEngine(
        params, cfg, kv_dtype=kv_dtype, page_size=ps,
        capacity_tokens=b * kvc.round_up(s + steps, ps),
        sample=sample, temperature=temperature, key=key,
        mesh=mesh, tp_int8_reduce=tp_int8_reduce, retain_pages=retain_pages,
        spec=spec)
    sids = [eng.submit(prompt[i], steps) for i in range(b)]
    outs = eng.run()
    return jnp.asarray([outs[sid] for sid in sids], jnp.int32)
