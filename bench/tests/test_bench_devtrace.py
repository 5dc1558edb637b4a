"""The trace reduction on a small recorded trace."""
import json
import os

import pytest

import tiny  # noqa: F401
import devtrace

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_small.json")


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _profile(path):
    """The fixture in the shape ``jax.profiler.ProfileData`` has."""
    with open(path) as f:
        raw = json.load(f)
    return _Obj(planes=[_Obj(name=p["name"], lines=[
        _Obj(name=l["name"], events=[
            _Obj(name=e[0], start_ns=e[1], duration_ns=e[2])
            for e in l["events"]]) for l in p["lines"]])
        for p in raw["planes"]])


def test_reduction_of_the_fixture():
    s = devtrace.reduce(_profile(FIXTURE))
    with open(FIXTURE) as f:
        want = json.load(f)["expected"]
    assert s.window_s == pytest.approx(want["window_s"])
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert s.chips == 1
    for name, sec in want["programs"].items():
        assert s.programs[name] == pytest.approx(sec)
    assert [g[0] for g in s.idle_gaps] == want["idle_labels"]
    assert s.idle_gaps[0][1] == pytest.approx(want["longest_gap_s"])
    assert s.top_ops[0][0] == want["top_program"]


def test_no_window_or_no_device_reads_nothing():
    p = _profile(FIXTURE)
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert devtrace.reduce(p) is None
    p = _profile(FIXTURE)
    for pl in p.planes:
        for ln in pl.lines:
            ln.events = [e for e in ln.events if e.name != "bench.window"]
    assert devtrace.reduce(p) is None


def test_program_names():
    assert devtrace.program_name("jit_camp_gemm_fused_w8a8(123)") == \
        "camp_gemm_fused_w8a8"
    assert devtrace.program_name("jit__paged_attention_pallas(7)") == \
        "_paged_attention_pallas"
