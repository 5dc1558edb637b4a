"""The profiler trace of a window, reduced to device time by kernel and
by program and op kind, and to idle gaps by what the host was doing.

The run starts JAX's profiler before the window and stops it after; the
window itself is a host span named ``bench.window``, and each engine step a
span ``bench.step``. From the ``.xplane.pb`` file this module reads:

- busy time: the union of the intervals of the device's ``XLA Ops`` events
  inside the window, averaged over the chips that have events;
- time by kernel: the ``XLA Ops`` events that are a Pallas kernel, summed
  by kernel, in whatever program they ran. Each such event is named by
  the text of its HLO instruction, ``%<name> = <shape> <opcode>(...)``. A
  ``pallas_call`` traced inside the ``jax.jit`` function ``K`` becomes a
  custom call with ``custom_call_target="tpu_custom_call"`` that XLA names
  ``K.<n>``, whether ``K`` is a program of its own or nested in a larger
  ``jax.jit``. So an op is kernel ``K``'s when its text holds that target
  and its name, less the ``.<n>`` suffix, is ``K``. The layout copies and
  pads XLA adds around the call are ops of their own (``copy.<n>``), not
  the kernel's;
- time by program and op kind: every other ``XLA Ops`` event, summed by
  ``<program>/<kind>``. The program is the ``XLA Modules`` event the op
  starts in, named with JAX's ``jit_`` prefix and the trailing ``(id)``
  taken off; the kind is the op's name less the ``.<n>`` suffix
  (``copy``, ``pad``, ``scatter``, ``broadcast_multiply_fusion``). So a
  paged kernel's relayout of the pool reads
  ``_paged_attention_pallas/copy`` and a KV write's copy ``scatter/copy``,
  and in a compiled step the step's name stands before each kind. An op
  in no module is keyed by its kind alone;
- the longest idle gaps, each named by the innermost host span that
  covers its middle.

Each time is clipped to the window and averaged over the chips.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_ID = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"\.\d+$")
_PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernels: Dict[str, float]            # kernel name -> device seconds
    top_ops: List[list]                  # [[kernel, or program/op kind,
                                         #   seconds]], longest first
    idle_gaps: List[list]                # [[host span, seconds]], longest first
    chips: int


def program_name(event_name: str) -> str:
    name = _ID.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_kind(event_name: str) -> str:
    """An ``XLA Ops`` event's HLO instruction name less its ``.<n>``
    suffix."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name)


def kernel_of(event_name: str) -> Optional[str]:
    """The Pallas kernel an ``XLA Ops`` event is, by the rule in the module
    docstring, or None."""
    return op_kind(event_name) if _PALLAS in event_name else None


def start(bench_dir: str) -> str:
    """Start the profiler into a fixed directory in the checkout."""
    import jax
    d = os.path.join(os.path.dirname(bench_dir), ".bench_cache", "trace")
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def stop_and_reduce(d: str) -> Optional[Summary]:
    import jax
    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(files[-1]))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce(profile) -> Optional[Summary]:
    """Reduce a ``ProfileData`` (or anything with the same ``planes`` /
    ``lines`` / ``events`` shape) to a :class:`Summary`."""
    window = None
    host_spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if e.name == WINDOW_SPAN:
                        window = (a, b)
                    else:
                        host_spans.append((a, b, e.name))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            kernel_of(e.name), op_kind(e.name))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                   program_name(e.name))
                                  for e in line.events)
            if ops or mods:
                devices.append((ops, mods))
    if window is None or not devices:
        return None
    lo, hi = window
    busy_total = 0.0
    kernels: Dict[str, float] = {}
    kinds: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []

    def add(into, key, a, b):
        a, b = _clip(a, b, lo, hi)
        if b > a:
            into[key] = into.get(key, 0.0) + (b - a) * 1e-9

    for ops, mods in devices:
        clipped = [_clip(a, b, lo, hi) for a, b, *_ in ops or mods]
        merged = _union([(a, b) for a, b in clipped if b > a])
        busy_total += sum(b - a for a, b in merged)
        prev = lo
        for a, b in merged:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        starts = [m[0] for m in mods]
        for a, b, kernel, kind in ops:
            if kernel is not None:
                add(kernels, kernel, a, b)
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < mods[i][1]:
                kind = f"{mods[i][2]}/{kind}"
            add(kinds, kind, a, b)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    host_spans.sort(key=lambda s: s[1] - s[0])       # innermost first
    idle = []
    for a, b in gaps[:10]:
        mid = (a + b) / 2
        label = next((name for s, e, name in host_spans if s <= mid <= e),
                     "no host span")
        idle.append([label, (b - a) * 1e-9])
    top = sorted(([k, v / n] for k, v in [*kernels.items(), *kinds.items()]),
                 key=lambda kv: -kv[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   kernels={k: v / n for k, v in kernels.items()},
                   top_ops=top, idle_gaps=idle, chips=n)
