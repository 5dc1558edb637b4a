"""A tiny checkout for the CPU tests: the real harness, readers and
reference, a tiny configuration and mix, and a dummy metric, all added as
files beside the real ones."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.chat"
MODEL = {
    "source": "test", "reference": "qwen2_ref", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
    "serving": {"weights": "w8a8", "kv_cache": "int8", "sampling": "greedy"},
    "pool": {"capacity_tokens": 16384},
    # a sound tiny run reads gaps of a few hundredths (the chip limits
    # are set in the real configuration files)
    "correct": {"max_logit_gap": 0.05, "min_tokens": 10,
                "sample": {"requests": 64, "tokens": 2000}},
    "control": {"weights": "w4a8"},
}
# Served over a tensor-parallel mesh of 4 chips: heads, kv heads, d_ff and
# the vocabulary all divide by 4, and the head is untied (a CAMP GEMM whose
# columns shard), so every weight the serve rules split is split.
TP_MODEL = dict(MODEL, num_attention_heads=8, num_key_value_heads=4,
                intermediate_size=256, tie_word_embeddings=False,
                serving=dict(MODEL["serving"], tensor_parallel=4))
MIX = {
    "arrivals": {"process": "poisson", "rate_per_s": 4.0},
    "requests": 40, "sizes_seed": 3,
    "prefix": {"tokens": 64, "pool": 2, "zipf_s": 1.0},
    "prompt_tokens": {"median": 16, "sigma": 0.5, "min": 4, "max": 64},
    "output_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
}
# Every request due at once (12 within 17 ms). The engine admits one prompt
# a step, so the decode batch grows by a row a step until the queue is
# empty, however long a step takes: a faster engine still batches here.
BURST = dict(MIX, arrivals={"process": "poisson", "rate_per_s": 1000.0},
             requests=12,
             output_tokens={"median": 16, "sigma": 0.5, "min": 8, "max": 32})
DUMMY_METRIC = '''"""Steps in the window: a metric added by a file alone."""


def read(run):
    return float(len(run.window_steps()))
'''


def checkout(tmp: str, model=None, mix=None, chips: int = 1) -> str:
    """A checkout under ``tmp`` whose BENCHMARK.json names the tiny cell, on
    ``chips`` chips."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(model or MODEL, f)
    with open(os.path.join(bench, "traffic", "tiny-chat.json"), "w") as f:
        json.dump(mix or MIX, f)
    with open(os.path.join(bench, "metrics", "window_steps.py"), "w") as f:
        f.write(DUMMY_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "tiny-chat", "chips": chips,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        m.setdefault("workloads", []).append(CELL)
    spec["per_layer"].append({"name": "window_steps", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "itl_p95_ms",
                              "workloads": [CELL]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp


def run(tmp: str, *, seed=7, seconds=2.0, traced=False, qmode=None,
        log=lambda m: None, model=None, mix=None, full=False, chips=1,
        warmup=True):
    """One harness run of the tiny cell on the CPU, past the chip check:
    the result object, or with ``full`` the pair (result, Run)."""
    import time
    import harness
    root = checkout(tmp, model, mix, chips)
    cell = harness.find_cell(harness.load_benchmark(root), CELL, root)
    real = harness.device_record
    harness.device_record = lambda chips, table: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
    try:
        out = harness.run_cell(cell, seed, seconds, traced,
                               t_process=time.perf_counter(),
                               log=log, qmode=qmode, warmup=warmup)
        return out if full else out[0]
    finally:
        harness.device_record = real
