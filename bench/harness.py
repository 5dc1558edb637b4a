"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

- the configuration file named by the config entry (``bench/configs/``), and
  its plain reference, the module named by the file's ``reference`` key;
- the traffic mix ``bench/traffic/<traffic>.json``, read by ``traffic.py``;
- every metric, end to end or per layer, as ``bench/metrics/<name>.py``, a
  reader with ``read(run)`` that returns a number, or None when it finds
  nothing to read (the metric is then left out of the line).

A run: set-up (weights from the seed, the engine and its pool, a warm-up of
twins of the window's own requests on that engine, so that every shape the
window uses is built and every shared prefix is in the prefix trie), the
measured window on the same engine, then the check of what the window served
against the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
AUTOTUNE_PATH = os.path.join(ROOT, ".bench_cache", "autotune.json")
TTFT_GRACE_S = 60.0        # wait past the window for first tokens still due


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding the pieces by name
# ---------------------------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict               # the entry in BENCHMARK.json
    model: dict                # the configuration file
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str

    def reference(self):
        return _load_module(
            os.path.join(self.bench_dir, "configs",
                         self.model["reference"] + ".py"),
            "bench_ref_" + self.model["reference"])

    def reader(self, metric: str):
        return _load_module(
            os.path.join(self.bench_dir, "metrics", metric + ".py"),
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"))


def find_cell(bench: dict, name: str, root: str = ROOT) -> Cell:
    works = {w["name"]: w for w in bench["workloads"]}
    if name not in works:
        raise KeyError(f"no workload {name!r}; known: {sorted(works)}")
    w = works[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        model = json.load(f)
    bench_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        cfg["file"])))
    from traffic import load_mix
    mix = load_mix(w["traffic"], os.path.join(bench_dir, "traffic"))

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name, w, cfg, model, mix,
                [m for m in bench["end_to_end"] if applies(m)],
                [m for m in bench["per_layer"] if applies(m)], bench_dir)


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_lengths: List[int]            # cached tokens per decoded row
    prefill: List[tuple]                 # (q_start, chunk) per prefill chunk
    prefill_done: int                    # prompts whose last chunk ran
    waiting: int                         # requests queued after the step


@dataclasses.dataclass
class Served:
    index: int
    due: float                           # seconds from window start
    prompt_len: int
    max_new: int
    matched: int = 0                     # prompt tokens found in the trie
    admitted: bool = False
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    cell: str
    seconds: float
    window: tuple                        # (start, end) on the host clock
    steps: List[Step]
    served: List[Served]
    pool_samples: List[tuple]            # (t, held, written, retained pages)
    counters: Dict[str, float]
    shape: object                        # counting.Shape
    peaks: dict
    setup_s: float
    trace: Optional[object] = None       # trace.Summary in a traced run

    def window_steps(self) -> List[Step]:
        a, b = self.window
        return [s for s in self.steps if a <= s.t0 < b]


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------
def prepare_process() -> None:
    """Caches at fixed paths in the checkout, before anything compiles: the
    compilation cache, so that only a cell's first run in a checkout
    compiles, and an autotune cache that starts empty in every run, so every
    run serves from the same analytic block choices. Registers the kernel
    gate's trace counter before anything is traced."""
    os.makedirs(os.path.dirname(AUTOTUNE_PATH), exist_ok=True)
    if os.path.exists(AUTOTUNE_PATH):
        os.remove(AUTOTUNE_PATH)
    os.environ["REPRO_AUTOTUNE_CACHE"] = AUTOTUNE_PATH
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # the eager engine's programs mostly compile in well under a second;
    # JAX's default (1 s) would compile them again in every run; a run
    # builds some thousands of them, so no size cap may evict its own
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    kernel_traces()


def device_record(chips: int, peaks_table: dict) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks_table["devices"]:
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def model_config(cell: Cell, qmode: Optional[str] = None):
    """The program's ModelConfig of the whole model (a tensor-parallel
    engine takes the whole model and shards it; ``count_shape`` counts one
    chip's share)."""
    from repro.models.config import ModelConfig
    ref = cell.reference()
    sh = ref.model_shape(cell.model)
    return ModelConfig(
        name=cell.config["name"], family="dense",
        n_layers=sh["n_layers"], d_model=sh["d_model"],
        n_heads=sh["n_heads"], n_kv_heads=sh["n_kv_heads"],
        head_dim=sh["head_dim"], d_ff=sh["d_ff"], vocab_size=sh["vocab"],
        qkv_bias=True, tie_embeddings=sh["tied"],
        rope_theta=sh["rope_theta"], norm_eps=sh["eps"],
        qmode=qmode or cell.model["serving"]["weights"])


def tensor_parallel(cell: Cell, mc) -> int:
    """The tensor-parallel degree the configuration states
    (``serving.tensor_parallel``, 1 where absent). Refused, before anything
    is built, where it is not the cell's chip count, or where it does not
    divide the kv heads: the engine would then quietly run attention
    replicated on every chip."""
    tp = int(cell.model["serving"].get("tensor_parallel", 1))
    chips = int(cell.workload["chips"])
    if tp != chips:
        raise ValueError(f"{cell.name}: serving.tensor_parallel {tp} is not "
                         f"the cell's {chips} chips")
    if mc.n_kv_heads % tp:
        raise ValueError(f"{cell.name}: serving.tensor_parallel {tp} does "
                         f"not divide the {mc.n_kv_heads} kv heads")
    return tp


def _serve_split(mc, tp: int) -> dict:
    """Whether the serve rules shard d_ff (the gate projection's columns)
    and the vocabulary (the head's columns; the embedding's rows where the
    head is tied to it) over a model axis of ``tp``, as they place the
    weights: read from ``params_pspecs`` over the weights' shapes alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    from repro.models.transformer import init_layer, init_top, quantize_params
    from repro.parallel.sharding import make_rules, params_pspecs

    def weights(keys):
        p = init_top(keys, mc)
        p["layers"] = [init_layer(keys[2], mc, mc.mixer_of(0), mc.ffn_of(0))]
        return quantize_params(p, mc, mc.qmode)
    shapes = jax.eval_shape(weights, jax.ShapeDtypeStruct((3, 2), jnp.uint32))
    specs = params_pspecs(shapes, make_rules("serve"),
                          AbstractMesh((1, tp), ("data", "model")))

    def spec(leaf):
        return getattr(leaf, "q", leaf)
    head = (spec(specs["embedding"])[0] if mc.tie_embeddings
            else spec(specs["lm_head"])[-1])
    gate = spec(specs["layers"][0]["mlp"]["w_gate"])[-1]
    return {"d_ff": gate == "model", "vocab": head == "model"}


def count_shape(mc, tp: int = 1):
    """One chip's share of the model as it is run (``counting.Shape``). At
    degree ``tp`` the heads and kv heads are divided by ``tp``, and d_ff and
    the vocabulary where the serve rules shard them."""
    from counting import Shape
    bits = 4 if mc.qmode.startswith("w4") else 8
    heads, kv, d_ff, vocab = mc.n_heads, mc.n_kv_heads, mc.d_ff, mc.vocab_size
    if tp > 1:
        split = _serve_split(mc, tp)
        heads, kv = heads // tp, kv // tp
        d_ff = d_ff // tp if split["d_ff"] else d_ff
        vocab = vocab // tp if split["vocab"] else vocab
    return Shape(mc.n_layers, mc.d_model, heads, kv, mc.hd, d_ff, vocab, bits,
                 mc.tie_embeddings, mc.qkv_bias)


def seed_key(seed: int):
    import jax
    return jax.random.PRNGKey(seed % 2 ** 32)


def serving_mesh(tp: int):
    """The (data 1, model ``tp``) mesh over the cell's chips, as
    ``python -m repro.launch.serve --tp`` builds it."""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(tp)
    if dict(mesh.shape) != {"data": 1, "model": tp}:
        raise NoChip(f"a tensor-parallel degree of {tp} runs on exactly "
                     f"{tp} chips, JAX has {mesh.size}")
    return mesh


def build_engine(params, mc, cell: Cell, seed: int, **mesh):
    """The engine; ``mesh=`` serves over a tensor-parallel mesh, and at
    degree 1 no mesh is passed at all."""
    from repro.serving.engine import ContinuousBatchingEngine
    import jax
    return ContinuousBatchingEngine(
        params, mc, kv_dtype=cell.model["serving"]["kv_cache"],
        capacity_tokens=int(cell.model["pool"]["capacity_tokens"]),
        sample="greedy", key=jax.random.PRNGKey(seed % 2 ** 32), **mesh)


def assert_sharded(eng, tp: int) -> None:
    """The engine serves at degree ``tp``, its pool sharded over the chips
    when ``tp`` > 1: a configuration that quietly runs replicated does not
    read as tensor parallel."""
    if eng.tp != tp or eng.pool.sharded != (tp > 1):
        raise RuntimeError(f"engine serves at degree {eng.tp}, pool sharded "
                           f"{eng.pool.sharded}; the configuration states "
                           f"{tp}")


# ---------------------------------------------------------------------------
# Driving the engine
# ---------------------------------------------------------------------------
class Driver:
    """Submits requests to ``ContinuousBatchingEngine.submit`` once they are
    due, calls ``step()``, and stamps every token when ``step()`` returns
    (the engine syncs there: it reads the sampled tokens back to the host),
    and notes, around each step, what the step did."""

    def __init__(self, eng, requests):
        self.eng = eng
        self.todo = list(requests)
        self.next = 0
        self.all: Dict[int, tuple] = {}    # seq id -> (engine Request, Served)
        self.live: Dict[int, tuple] = {}   # admitted and not finished
        self.steps: List[Step] = []
        self.pool_samples: List[tuple] = []
        self.late: List[float] = []        # how late each submission ran, s

    @property
    def served(self) -> List[Served]:
        return [rec for _, rec in self.all.values()]

    def submit_due(self, t_rel: float) -> None:
        while self.next < len(self.todo):
            r = self.todo[self.next]
            due = r.due_s
            if due > t_rel:
                break
            sid = self.eng.submit(np.asarray(r.prompt, np.int32), r.max_new)
            pair = (self.eng.waiting[-1],
                    Served(r.index, due, len(r.prompt), r.max_new))
            self.all[sid] = pair
            self.late.append(t_rel - due)
            self.next += 1

    def next_due(self) -> Optional[float]:
        if self.next >= len(self.todo):
            return None
        return self.todo[self.next].due_s

    def busy(self) -> bool:
        e = self.eng
        return bool(e.waiting or e.prefilling or e.active)

    def step(self, clock) -> None:
        eng = self.eng
        head = eng.waiting[0] if eng.waiting and not eng.prefilling else None
        matched = (eng.pool.match_prefix(head.prompt_tokens)[0]
                   if head is not None else 0)
        before = {sid: (len(req.tokens), req.pos)
                  for sid, (req, _) in self.live.items()}
        t0 = clock()
        eng.step()
        t1 = clock()
        if head is not None and not (eng.waiting and eng.waiting[0] is head):
            req, rec = self.all[head.seq_id]   # admitted in this step
            rec.admitted, rec.matched = True, matched
            self.live[head.seq_id] = (req, rec)
            before[head.seq_id] = (0, matched)
        decode, prefill, done = [], [], 0
        for sid, (req, rec) in list(self.live.items()):
            n0, p0 = before[sid]
            if req.pos > p0:
                prefill.append((p0, req.pos - p0))
            gained = len(req.tokens) - n0
            if gained:
                rec.token_times.extend([t1] * gained)
                first = 1 if n0 == 0 else 0
                done += first
                # a decoded row caches the prompt and every token before
                # the one it samples
                decode.extend(rec.prompt_len + n0 + first + j
                              for j in range(gained - first))
            if req.done:
                del self.live[sid]
        self.steps.append(Step(t0, t1, decode, prefill, done,
                               len(eng.waiting)))
        self.pool_samples.append((t1,) + pool_pages(eng.pool))


def pool_pages(pool) -> tuple:
    """(held, written, retained) pages of the pool: slots in the live
    sequences' block tables, the pages their tokens fill, and pages kept
    only for the prefix trie."""
    ps = pool.page_size
    held = pool.shared_page_stats()["distinct_slots"]
    written = sum(-(-n // ps) for n in pool.lens.values())
    return held, written, pool.num_retained


def drive(drv: Driver, seconds: float, clock, annotate=None) -> tuple:
    """The open loop for ``seconds`` on ``clock``: submit what is due, step
    while there is work, else sleep until the next arrival. Returns
    (start, end)."""
    start = clock()
    while True:
        t = clock() - start
        if t >= seconds:
            break
        drv.submit_due(t)
        if drv.busy():
            if annotate is None:
                drv.step(clock)
            else:
                with annotate("bench.step"):
                    drv.step(clock)
            continue
        nd = drv.next_due()
        if nd is None:
            break
        wait = min(nd, seconds) - t
        if wait > 0:
            if annotate is None:
                time.sleep(wait)
            else:
                with annotate("bench.wait_for_arrival"):
                    time.sleep(wait)
    return start, clock()


def warm_up_requests(twins, eng) -> list:
    """Twins of the window's requests (same sizes and prefixes, user turns
    of their own), all due at once. The engine admits one prompt at a time
    and a prompt takes one step a chunk, the part found in the prefix trie
    aside; each twin decodes until the step in which the last twin gets its
    first token, and all end in that step. So the decode batch passes every
    size the window's can reach, every prefill chunk the window will run is
    built, and the window opens on an idle engine whose prefix trie holds
    every shared prefix."""
    ps, chunk = eng.pool.page_size, eng.chunk_tokens
    seen, step, first = set(), 0, []
    for r in twins:
        matched = (r.prefix_tokens // ps * ps
                   if r.prefix_id >= 0 and r.prefix_id in seen else 0)
        seen.add(r.prefix_id)
        step += -(-(len(r.prompt) - matched) // chunk)
        first.append(step)
    return [dataclasses.replace(r, due_s=0.0, max_new=first[-1] - f + 2)
            for r, f in zip(twins, first)]


def finish_first_tokens(drv: Driver, counts, clock) -> None:
    """After the window: keep stepping, submitting nothing more, until every
    request the window counts has its first token, for at most a minute."""
    limit = clock() + TTFT_GRACE_S
    while clock() < limit and drv.busy():
        if all(rec.token_times for rec in drv.served if counts(rec)):
            break
        drv.step(clock)


# ---------------------------------------------------------------------------
# The check against the plain reference
# ---------------------------------------------------------------------------
def draw_sample(drv: Driver, seed: int, spec: dict) -> List[tuple]:
    """(prompt, served tokens) of a seeded sample of the requests that
    served tokens, the one with the most tokens always in it, up to
    ``spec["requests"]`` requests and ``spec["tokens"]`` served tokens."""
    got = sorted(((rec.index, req) for req, rec in drv.all.values()
                  if req.tokens), key=lambda x: x[0])
    if not got:
        return []
    reqs = [r for _, r in got]
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i].tokens))
    rng = np.random.default_rng((seed + 0x9E37) % 2 ** 64)
    order = [longest] + [int(i) for i in rng.permutation(len(reqs))
                         if i != longest]
    out, total = [], 0
    for i in order:
        if len(out) >= int(spec["requests"]):
            break
        toks = list(reqs[i].tokens)
        if out and total + len(toks) > int(spec["tokens"]):
            continue
        out.append((list(reqs[i].prompt_tokens), toks))
        total += len(toks)
    return out


def compare(cell: Cell, seed: int, sample: List[tuple]) -> dict:
    """The widest gap of a served token's reference logit below the
    reference's best, over the sample, beside its limit."""
    ref = cell.reference()
    lim = cell.model["correct"]
    if not sample:
        return {"correct": False, "numbers": {
            "served_tokens": [0, "> 0"]}}
    gaps = ref.check(cell.model, seed_key(seed),
                     {"prompts": [p for p, _ in sample],
                      "served": [s for _, s in sample]})
    widest = float(np.max(gaps))
    ok = bool(np.isfinite(widest) and widest <= lim["max_logit_gap"])
    return {"correct": ok, "compared_tokens": int(gaps.size),
            "numbers": {"max_logit_gap": [widest, lim["max_logit_gap"]],
                        "compared_tokens": [int(gaps.size),
                                            int(lim["min_tokens"])]},
            "ok_tokens": int(gaps.size) >= int(lim["min_tokens"])}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def _peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class KernelTraces:
    """How often JAX traced each function into a program in this process,
    by name, from ``jax.monitoring``: JAX reports a jaxpr trace for every
    ``jax.jit`` function it traces, one called only inside another
    ``jax.jit`` included, and a program loaded from the persistent cache is
    traced all the same. A shape traced once is served from JAX's trace
    cache after that and reported no more, so the counts hold for the
    whole process: runs take the one :func:`kernel_traces` gives."""
    EVENT = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_trace)

    def _on_trace(self, name, secs, fun_name=None, **_):
        if name == self.EVENT:
            self.counts[fun_name] = self.counts.get(fun_name, 0) + 1


_TRACES: Optional[KernelTraces] = None


def kernel_traces() -> KernelTraces:
    """The process's one :class:`KernelTraces`, registered on first use:
    :func:`prepare_process` calls it before anything is traced."""
    global _TRACES
    if _TRACES is None:
        _TRACES = KernelTraces()
    return _TRACES


def kernel_resolution() -> dict:
    """How the served path resolves its kernels on this machine."""
    from repro.kernels import ops
    from repro.kernels.backend import interpret_mode, resolve, resolve_paged
    return {"fused_gemm": resolve("auto", ops._VALID),
            "paged": resolve_paged("auto", True),
            "interpret": interpret_mode()}


def assert_compiled_kernels(qmode: str, traces: KernelTraces) -> dict:
    """Every kernel on the served path resolved to compiled Pallas and was
    traced into a program the process built, as its own program or inside
    a larger one: the fused CAMP GEMM of ``qmode`` and both paged attention
    kernels. Returns how often each was traced."""
    resolved = kernel_resolution()
    if resolved != {"fused_gemm": "pallas", "paged": "pallas",
                    "interpret": False}:
        raise RuntimeError(f"kernels not compiled Pallas: {resolved}")
    kernels = ("camp_gemm_fused_" + qmode, "_paged_attention_pallas",
               "_paged_prefill_pallas")
    counts = {k: traces.counts.get(k, 0) for k in kernels}
    idle = [k for k, v in counts.items() if v == 0]
    if idle:
        raise RuntimeError(f"kernels never compiled on the path: {idle}")
    return counts


class CompileCounter:
    """Backend compiles, from ``jax.monitoring``: JAX reports a backend
    compile for every program it builds, a persistent-cache hit included,
    so the hits are counted apart and taken off."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.built = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_build(self, name, secs, **_):
        if name == self.EVENT:
            self.built += 1
            self.seconds += secs

    def _on_event(self, name, **_):
        if name == self.HIT:
            self.hits += 1

    @property
    def n(self) -> int:
        return self.built - self.hits


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, log=print, qmode: Optional[str] = None,
             warmup: bool = True, rate: Optional[float] = None):
    """Set up, measure, check; returns (the result object, the Run).

    ``qmode`` serves another weight format than the configuration states
    (the control), still checked against the stated one; ``warmup=False``
    skips the warm-up, for runs that read only the check; ``rate`` offers
    another arrival rate than the mix (the knee sweep)."""
    import jax

    from repro.launch.serve import init_serving_params
    import traffic as traffic_mod

    mc = model_config(cell, qmode)
    tp = tensor_parallel(cell, mc)
    with open(os.path.join(cell.bench_dir, "peaks.json")) as f:
        peaks_table = json.load(f)
    device = device_record(int(cell.workload["chips"]), peaks_table)
    peaks = peaks_table["devices"][device["kind"]]
    compiles = CompileCounter()
    traces = kernel_traces()
    shape = count_shape(mc, tp)
    clock = time.perf_counter

    # at degree 1 neither call gets a mesh argument: the one-chip path
    mesh = {} if tp == 1 else {"mesh": serving_mesh(tp)}
    params = init_serving_params(seed_key(seed), mc, mc.qmode, **mesh)
    jax.block_until_ready(params)
    t_weights = clock() - t_process

    eng = build_engine(params, mc, cell, seed, **mesh)
    assert_sharded(eng, tp)
    reqs = traffic_mod.generate(cell.mix, seed, mc.vocab_size, rate=rate)
    t_warm0 = clock()
    warm = Driver(eng, [])
    if warmup:
        twins = traffic_mod.generate(cell.mix, seed, mc.vocab_size,
                                     rate=rate, stream=1)
        warm = Driver(eng, warm_up_requests(
            [r for r in twins if r.due_s < seconds], eng))
        warm.submit_due(0.0)
        while warm.busy():
            warm.step(clock)
    t_warm = clock() - t_warm0
    jax.block_until_ready(eng.pool.k_pages)
    setup_s = clock() - t_process
    pool_open = pool_pages(eng.pool)
    log(f"[bench] {cell.name}: weights {t_weights:.3f}s, warm-up "
        f"{t_warm:.3f}s ({len(warm.steps)} steps, decode batch up to "
        f"{max((len(s.decode_lengths) for s in warm.steps), default=0)} "
        f"of {len(warm.all)}), "
        f"set-up {setup_s:.3f}s; compiles so far {compiles.n}, cache hits "
        f"{compiles.hits} ({compiles.seconds:.3f}s in all); cache "
        f"{jax.config.jax_compilation_cache_dir}")
    drv = Driver(eng, reqs)

    trace_dir = None
    annotate = None
    if traced:
        import devtrace as trace_mod
        trace_dir = trace_mod.start(cell.bench_dir)
        annotate = jax.profiler.TraceAnnotation
    n_compiles0, hits0 = compiles.n, compiles.hits
    if annotate is not None:
        with annotate("bench.window"):
            start, end = drive(drv, seconds, clock, annotate=annotate)
    else:
        start, end = drive(drv, seconds, clock)
    n_compiles = compiles.n - n_compiles0
    hits = compiles.hits - hits0
    pool_close = pool_pages(eng.pool)
    pool_size = eng.pool.num_pages
    summary = None
    if traced:
        summary = trace_mod.stop_and_reduce(trace_dir)
    # due before the close, though the loop was busy
    drv.submit_due(np.nextafter(seconds, 0.0))

    def counts(rec):        # the requests this window answers for
        return rec.due < seconds
    finish_first_tokens(drv, counts, clock)
    memory_peak = _peak_bytes()
    if device["platform"] == "tpu":
        log(f"[bench] Pallas kernels traced into programs "
            f"{assert_compiled_kernels(mc.qmode, traces)}")

    for rec in drv.served:                 # host clock -> window-relative s
        rec.token_times = [t - start for t in rec.token_times]
    run = Run(cell.name, seconds, (start, end), drv.steps, drv.served,
              drv.pool_samples,
              {"compiles_in_window": n_compiles,
               "warm_up_steps": len(warm.steps),
               "warm_up_batch": max((len(s.decode_lengths)
                                     for s in warm.steps), default=0),
               "ttft_grace_s": TTFT_GRACE_S,
               "submit_late_p99_s": (float(np.percentile(drv.late, 99))
                                     if drv.late else 0.0)},
              shape, peaks, setup_s, summary)

    sample = draw_sample(drv, seed, cell.model["correct"]["sample"])
    attempted = sum(1 for r in run.served if counts(r))
    failed = sum(1 for r in run.served if counts(r) and not r.token_times)
    del drv, warm, eng, params       # warm and drv both hold the engine
    gc.collect()
    t_ref0 = clock()
    verdict = compare(cell, seed, sample)
    t_ref = clock() - t_ref0

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = cell.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    if summary is not None:
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    numbers = verdict["numbers"]
    correct = bool(verdict["correct"] and verdict.get("ok_tokens", False))
    done = sum(1 for r in run.served if len(r.token_times) >= r.max_new
               and r.token_times[-1] <= seconds)
    log(f"[bench] window {end - start:.3f}s, {len(run.window_steps())} "
        f"steps, {attempted} requests due, {done} finished in it, {failed} "
        f"without a first token, compiles in window {n_compiles} (and {hits} "
        f"programs loaded from the cache); pool "
        f"pages (held, written, retained) of {pool_size}: at the open "
        f"{pool_open}, at the close {pool_close}; reference {t_ref:.3f}s; "
        f"submissions late by p99 {run.counters['submit_late_p99_s']:.4f}s")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.top_ops[:10],
                            "idle_gaps": summary.idle_gaps[:10]}
    out["checked"] = {k: {"value": v[0], "limit": v[1]}
                      for k, v in numbers.items()}
    return out, run
