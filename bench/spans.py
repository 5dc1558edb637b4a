"""The serving engine's spans in the profile of a traced window.

The engine opens a span at each host-side phase of a step (``serve.step``,
``serve.admit``, ``serve.prefill_chunk``, ``serve.decode``, and inside the
lanes ``serve.forward`` and ``serve.sample``; ``repro.serving.tracing``).
While JAX's profiler records, which it does over a traced run's window,
each span is a host event of the profile with its attributes as stats:
``seq_id``, ``matched``, ``q_start``, ``tokens``, ``rows``, and ``build_s``,
``compiles`` and ``cache_loads`` where programs were built. So they sit on
the device trace's own clock.

This module reads them back from the profile the run left in
``<checkout>/.bench_cache/trace``, with the device's ``XLA Ops`` and ``XLA
Modules`` events, and writes what it found to
``<checkout>/.bench_cache/spans.json``. A program without these spans
leaves none to read, and the readers built on this module return None.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional

from devtrace import WINDOW_SPAN, _union

PREFIX = "serve."
_CACHE: Dict[tuple, "Spans"] = {}


@dataclasses.dataclass
class Span:
    name: str
    start_s: float                       # from the window's start
    end_s: float
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


@dataclasses.dataclass
class Spans:
    window_s: float
    spans: List[Span]                    # ``serve.*`` spans in the window
    chips: int                           # device planes with events
    # device idle seconds of the window (mean over chips), split three ways
    idle_in_forward_s: Optional[float] = None
    idle_in_step_outside_forward_s: Optional[float] = None
    idle_outside_steps_s: Optional[float] = None
    programs_per_step: Optional[List[float]] = None

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def read(run, reader_file: str) -> Optional[Spans]:
    """The spans of ``run``'s traced window, found from the path of the
    reader that asks (``<checkout>/bench/metrics/<name>.py``); None when
    the profile holds no window or no engine span."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
    cache = os.path.join(root, ".bench_cache")
    files = sorted(glob.glob(os.path.join(cache, "trace", "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        return None
    key = (files[-1], os.path.getmtime(files[-1]))
    if key not in _CACHE:
        from jax.profiler import ProfileData
        got = reduce(ProfileData.from_file(files[-1]))
        with open(os.path.join(cache, "spans.json"), "w") as f:
            json.dump(None if got is None else dataclasses.asdict(got), f)
        _CACHE[key] = got
    got = _CACHE[key]
    return got if got is not None and got.spans else None


def _overlap(xs, ys) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(profile) -> Optional[Spans]:
    """Reduce a ``ProfileData`` (or anything with the same ``planes`` /
    ``lines`` / ``events`` / ``stats`` shape) to :class:`Spans`; None
    without a window span."""
    window = None
    host = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name, dict(e.stats)))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
            if ops:
                devices.append((ops, lines.get("XLA Modules", [])))
    if window is None:
        return None
    lo, hi = window
    host = sorted((h for h in host if lo <= h[0] < hi),
                  key=lambda h: (h[0], -h[1]))   # outer span first
    out = Spans(window_s=(hi - lo) * 1e-9, chips=len(devices), spans=[
        Span(name, (a - lo) * 1e-9, (b - lo) * 1e-9, attrs)
        for a, b, name, attrs in host])
    if not devices:
        return out
    steps = [(a, min(b, hi)) for a, b, name, _ in host
             if name == "serve.step"]
    in_steps = _union(steps)
    forwards = _union((a, min(b, hi)) for a, b, name, _ in host
                      if name == "serve.forward")
    in_fwd = in_step = idle = 0.0
    per_step = [0.0] * len(steps)
    for ops, mods in devices:
        busy = _union((max(a, lo), min(b, hi)) for a, b in ops
                      if min(b, hi) > max(a, lo))
        gaps, prev = [], lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        idle += sum(b - a for a, b in gaps)
        in_fwd += _overlap(gaps, forwards)
        in_step += _overlap(gaps, in_steps)
        starts = sorted(a for a, _ in mods)
        for k, (a, b) in enumerate(steps):
            per_step[k] += (bisect.bisect_left(starts, b)
                            - bisect.bisect_left(starts, a))
    n = len(devices)
    out.idle_in_forward_s = in_fwd * 1e-9 / n
    out.idle_in_step_outside_forward_s = (in_step - in_fwd) * 1e-9 / n
    out.idle_outside_steps_s = (idle - in_step) * 1e-9 / n
    out.programs_per_step = [c / n for c in per_step]
    return out
