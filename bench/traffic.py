"""The one traffic generator: reads a mix file and makes a cell's requests.

A mix is a JSON file under ``bench/traffic/``, found by the name the cell
gives. Its keys:

- ``arrivals``: ``{"process": "poisson", "rate_per_s": r}``, an open loop;
- ``requests``: how many requests the mix holds;
- ``sizes_seed``: draws the sizes, their order and the arrival instants
  once, so every run seed serves the same requests at the same times and
  the window's shapes repeat from run to run;
- ``prompt_tokens`` / ``output_tokens``: lognormal lengths,
  ``{"median", "sigma", "min", "max"}``; the prompt length is the user turn
  that follows the prefix;
- ``prefix`` (optional): ``{"tokens": n, "pool": k, "zipf_s": s}`` puts one of
  ``k`` shared prefixes of ``n`` tokens, chosen with Zipf popularity, before
  every prompt.

The run seed draws every token id, the prefixes' included, uniformly from
the vocabulary. ``stream`` > 0 draws the user turns from another stream of
the same seed, over the same prefixes: requests of the same sizes that share
only the prefixes with stream 0's (the harness warms up on them).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


@dataclasses.dataclass
class Request:
    index: int
    due_s: float
    prompt: List[int]
    max_new: int
    prefix_id: int                  # -1: no shared prefix
    prefix_tokens: int


def load_mix(name: str, directory: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(directory, name + ".json")) as f:
        return json.load(f)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def sizes(mix: dict):
    """(prefix ids, prompt lengths, output lengths, arrival instants at one
    request per second): fixed by the mix, the same for every seed."""
    n = int(mix["requests"])
    rng = np.random.default_rng(int(mix["sizes_seed"]))
    prompt = _lengths(rng, mix["prompt_tokens"], n)
    output = _lengths(rng, mix["output_tokens"], n)
    pre = mix.get("prefix")
    if pre:
        k = int(pre["pool"])
        p = 1.0 / np.arange(1, k + 1) ** float(pre.get("zipf_s", 1.0))
        prefix = rng.choice(k, size=n, p=p / p.sum())
    else:
        prefix = np.full(n, -1)
    unit = np.cumsum(rng.exponential(1.0, n))
    return prefix, prompt, output, unit


def generate(mix: dict, seed: int, vocab: int, *,
             rate: Optional[float] = None, stream: int = 0) -> List[Request]:
    """The cell's requests, in arrival order, with token ids from ``seed``;
    ``rate`` overrides the mix's arrival rate."""
    prefix, prompt, output, unit = sizes(mix)
    n = len(prompt)
    rng = np.random.default_rng(seed % 2 ** 64)
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    due = unit / float(rate if rate is not None else arr["rate_per_s"])
    pre = mix.get("prefix")
    n_pre = int(pre["tokens"]) if pre else 0
    shared = {k: rng.integers(0, vocab, n_pre).tolist()
              for k in range(int(pre["pool"]))} if pre else {}
    if stream:
        rng = np.random.default_rng([seed % 2 ** 64, stream])
    out = []
    for j in range(n):
        pid = int(prefix[j])
        head = shared[pid] if pid >= 0 else []
        body = rng.integers(0, vocab, int(prompt[j])).tolist()
        out.append(Request(j, float(due[j]), head + body, int(output[j]),
                           pid, len(head)))
    return out
