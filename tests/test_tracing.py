"""The span-and-counter recorder of the serving engine."""
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro.serving import tracing


class _NoClock:
    """Stands in for the recorder's clock: may not be read."""

    @staticmethod
    def perf_counter():
        raise AssertionError("clock read")


class _Refused:
    """Stands in for ``jax.profiler.TraceAnnotation``: may not be built."""

    def __init__(self, *a, **kw):
        raise AssertionError("TraceAnnotation constructed")

    @staticmethod
    def is_enabled():
        return False


def test_spans_nest_with_parent_indices_and_notes():
    t = tracing.Tracer(enabled=True)
    with t.span("a", x=1):
        with t.span("b"):
            t.note(y=2)
            with t.span("c", z=3):
                pass
        with t.span("d"):
            pass
    t.note(lost=True)                    # no span open: nothing to add to
    assert [s.name for s in t.spans] == ["a", "b", "c", "d"]
    assert [s.parent for s in t.spans] == [-1, 0, 1, 0]
    assert [s.attrs for s in t.spans] == [{"x": 1}, {"y": 2}, {"z": 3}, {}]
    for s in t.spans:
        assert s.end is not None and s.start <= s.end
    a, b, c, d = t.spans
    assert a.start <= b.start <= c.start <= c.end <= b.end <= d.start
    assert d.end <= a.end


def test_a_compile_lands_on_the_innermost_open_span():
    t = tracing.Tracer(enabled=True)
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7.0)
    with t.span("outer"):
        with t.span("inner"):
            f(x).block_until_ready()                  # traces and compiles
        with t.span("again"):
            f(x).block_until_ready()                  # cached: builds nothing
    outer, inner, again = t.spans
    assert inner.attrs["compiles"] >= 1
    assert 0 < inner.attrs["build_s"] <= inner.end - inner.start
    assert "build_s" not in again.attrs and "compiles" not in again.attrs
    assert "build_s" not in outer.attrs


def test_nested_build_events_count_once():
    # a build event that ends after an earlier one and covers its start
    # (a trace inside a trace) replaces it: build_s is the union
    t = tracing.Tracer(enabled=True)
    with t.span("s"):
        op = t._open[-1]
        time.sleep(0.02)
        op.build(0.01)                   # [now-0.01, now]
        op.build(0.015)                  # contains the first
        time.sleep(0.01)
        op.build(0.005)                  # disjoint
    assert t.spans[0].attrs["build_s"] == pytest.approx(0.02, abs=2e-3)


def test_disabled_tracer_records_nothing_and_builds_no_annotation(
        monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    monkeypatch.setattr(tracing, "time", _NoClock)
    for t in (tracing.Tracer(), tracing.DEFAULT):
        first = t.span("a", x=1)
        with first:
            t.note(y=2)
            assert t.span("b") is first          # one shared no-op
            jax.jit(lambda v: v - 1)(jnp.ones(3)).block_until_ready()
        assert t.spans == [] and t._open == []
    t = tracing.Tracer(enabled=True)
    t.disable()
    with t.span("a"):
        pass
    assert t.spans == []


def test_default_tracer_writes_spans_into_a_running_profile(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.DEFAULT.span("serve.test", seq_id=4):
            tracing.DEFAULT.note(matched=128)
            jax.jit(lambda v: v * 5)(jnp.ones(5)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert tracing.DEFAULT.spans == []   # the profile is its only record
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(e.stats) for pl in ProfileData.from_file(path[-1]).planes
             if pl.name.startswith("/host:") for ln in pl.lines
             for e in ln.events if e.name == "serve.test"]
    assert len(found) == 1
    got = found[0]
    assert got["seq_id"] == 4 and got["matched"] == 128
    assert got["compiles"] >= 1 and got["build_s"] > 0
