"""The engine's spans read back from the profile of a traced window: the
reduction on a hand-written trace, and a tiny traced run whose spans agree
with what the harness reads from outside ``step()``."""
import importlib.util
import json
import os
import types

import pytest

import tiny
import devtrace
import spans

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_spans_small.json")
NEW = ("queue_wait_ms_p50", "prefill_chunk_ms_p50", "decode_ms_p50",
       "build_s_in_window", "programs_per_step", "dispatch_idle_share")


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _profile(path):
    """The fixture in the shape ``jax.profiler.ProfileData`` has."""
    with open(path) as f:
        raw = json.load(f)
    return _Obj(planes=[_Obj(name=p["name"], lines=[
        _Obj(name=l["name"], events=[
            _Obj(name=e[0], start_ns=e[1], duration_ns=e[2],
                 stats=list(e[3].items()) if len(e) > 3 else [])
            for e in l["events"]]) for l in p["lines"]])
        for p in raw["planes"]])


def _reader(name):
    path = os.path.join(tiny.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("spans_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reduction_of_the_spans_fixture(monkeypatch):
    """By hand, on the window [1000, 21000) ns:

    - device busy (XLA Ops clipped to the window) 7500 ns, idle 12500;
    - idle inside ``serve.forward`` ([1500, 4000), [5600, 7600),
      [12200, 17200)): 500 + 500, 400 + 500, 800 + 2000 = 4700 ns, so
      ``dispatch_idle_share`` = 4700 / 20000 = 23.5%;
    - idle inside ``serve.step`` ([1000, 9000), [12000, 20000)):
      4500 + 5000 = 9500 ns, 4800 of it outside ``serve.forward``;
      outside any step 3000 ns; 23.5 + 24 + 15 = 62.5% =
      ``device_idle_share``;
    - programs starting in the steps: 4 and 2, mean 3; the step at
      21500 and its program lie past the window;
    - one prefill chunk of 4000 ns; decodes of 3300 and 7000 ns, median
      5150 ns; ``build_s`` 2e-6 on one forward;
    - admissions at 100 ns (seq 7, due 0) and 11050 ns (seq 8, due
      2000 ns): waits 100 and 9050 ns; a third request, due at 15000 ns,
      is not admitted in the window and waits to its first token at 3 s:
      median 9050 ns.
    """
    sp = spans.reduce(_profile(FIXTURE))
    assert sp.window_s == pytest.approx(2e-5) and sp.chips == 1
    assert len(sp.named("serve.step")) == 2
    assert sp.idle_in_forward_s == pytest.approx(4.7e-6)
    assert sp.idle_in_step_outside_forward_s == pytest.approx(4.8e-6)
    assert sp.idle_outside_steps_s == pytest.approx(3.0e-6)
    assert sp.programs_per_step == [4.0, 2.0]
    assert sp.named("serve.admit")[0].attrs == {
        "seq_id": 7, "prompt_tokens": 300, "matched": 256}
    # the three parts add up to the existing reader's idle share
    s = devtrace.reduce(_profile(FIXTURE))
    idle = (sp.idle_in_forward_s + sp.idle_in_step_outside_forward_s
            + sp.idle_outside_steps_s)
    assert idle == pytest.approx(s.window_s - s.busy_s)
    run = _Obj(seconds=2e-5, trace=s, counters={"ttft_grace_s": 60.0},
               served=[_Obj(due=0.0, token_times=[5e-6]),
                       _Obj(due=2e-6, token_times=[]),
                       _Obj(due=1.5e-5, token_times=[3.0])])
    monkeypatch.setattr(spans, "read", lambda run, path: sp)
    want = {"queue_wait_ms_p50": 9.05e-3, "prefill_chunk_ms_p50": 4e-3,
            "decode_ms_p50": 5.15e-3, "build_s_in_window": 2e-6,
            "programs_per_step": 3.0, "dispatch_idle_share": 23.5}
    for name in NEW:
        assert _reader(name).read(run) == pytest.approx(want[name]), name
    assert _reader("device_idle_share").read(run) == pytest.approx(62.5)


def test_programs_per_step_is_a_mean_over_both_kinds_of_step(monkeypatch):
    # 3 decode-only steps and 2 with a prefill chunk: the median would be
    # the decode-only count; one step more with a chunk would flip it
    sp = spans.Spans(window_s=1.0, spans=[], chips=1,
                     programs_per_step=[4017.0] * 3 + [7606.0] * 2)
    monkeypatch.setattr(spans, "read", lambda run, path: sp)
    reader = _reader("programs_per_step")
    assert reader.read(_Obj()) == pytest.approx(5452.6)
    sp.programs_per_step.append(7606.0)
    assert reader.read(_Obj()) == pytest.approx(5811.5)


def test_a_profile_without_engine_spans_reads_nothing(monkeypatch):
    p = _profile(FIXTURE)
    for pl in p.planes:
        for ln in pl.lines:
            ln.events = [e for e in ln.events
                         if not e.name.startswith("serve.")]
    sp = spans.reduce(p)
    assert sp.spans == [] and sp.programs_per_step == []
    # the reader sees what ``read`` returns for such a profile: nothing
    monkeypatch.setattr(spans, "read", lambda run, path: None)
    for name in NEW:
        assert _reader(name).read(_Obj()) is None
    p.planes = [pl for pl in p.planes if pl.name.startswith("/host")]
    assert spans.reduce(p).idle_in_forward_s is None


def test_traced_tiny_run_spans_agree_with_outside_readings(tmp_path):
    # shared prefixes of two 128-token pages, so that admissions match some
    mix = dict(tiny.MIX, prefix=dict(tiny.MIX["prefix"], tokens=256))
    out, run = tiny.run(str(tmp_path), traced=True, full=True, mix=mix)
    assert out["correct"] is True, out["checked"]
    # no TPU plane on the CPU: the two device metrics stay out
    assert set(NEW[:4]) <= set(out["metrics"])
    assert not set(NEW[4:]) & set(out["metrics"])
    assert out["metrics"]["queue_wait_ms_p50"]["value"] > 0
    assert out["metrics"]["build_s_in_window"]["value"] >= 0
    sp = spans.read(run, os.path.join(str(tmp_path), "bench", "metrics",
                                      "queue_wait_ms_p50.py"))
    with open(tmp_path / ".bench_cache" / "spans.json") as f:
        assert len(json.load(f)["spans"]) == len(sp.spans)

    # prefix matches: the window's admissions in the order of submission
    admits = sorted(sp.named("serve.admit"), key=lambda s: s.attrs["seq_id"])
    assert admits and [s.attrs["seq_id"] for s in admits] == list(range(
        admits[0].attrs["seq_id"], admits[0].attrs["seq_id"] + len(admits)))
    recs = run.served[:len(admits)]
    assert all(r.admitted for r in recs)
    assert [s.attrs["matched"] for s in admits] == [r.matched for r in recs]
    assert any(r.matched > 0 for r in recs)
    assert [s.attrs["prompt_tokens"] for s in admits] == \
        [r.prompt_len for r in recs]

    # prefill chunks and decode rows, step by step
    steps = sp.named("serve.step")
    window = run.window_steps()
    assert len(steps) == len(window) > 0
    assert any(r.prefill for r in window)
    assert any(r.decode_lengths for r in window)

    def inside(name, st):
        return [s for s in sp.named(name)
                if st.start_s <= s.start_s and s.end_s <= st.end_s]
    for st, rec in zip(steps, window):
        chunks = [(s.attrs["q_start"], s.attrs["tokens"])
                  for s in inside("serve.prefill_chunk", st)]
        assert chunks == rec.prefill
        rows = [s.attrs["rows"] for s in inside("serve.decode", st)]
        assert rows == ([len(rec.decode_lengths)] if rec.decode_lengths
                        else [])
