"""The trace reduction on small recorded traces."""
import json
import os

import pytest

import tiny  # noqa: F401
import devtrace

HERE = os.path.dirname(__file__)
# the first written by hand in the chip trace's layout; the second cut from
# a chip trace (its note says how it was recorded)
FIXTURES = ["trace_small.json", "trace_nested_chip.json"]


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


def _expected(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)["expected"]


@pytest.mark.parametrize("fixture", FIXTURES)
def test_reduction_of_the_fixture(fixture):
    s = devtrace.reduce(_profile(os.path.join(HERE, fixture)))
    want = _expected(fixture)
    assert s.window_s == pytest.approx(want["window_s"])
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert s.chips == 1
    assert s.kernels == pytest.approx(want["kernels"])
    tops = dict(s.top_ops)
    for kind, sec in want["op_kinds"].items():
        assert tops[kind] == pytest.approx(sec)
    assert [g[0] for g in s.idle_gaps] == want["idle_labels"]
    assert s.idle_gaps[0][1] == pytest.approx(want["longest_gap_s"])
    assert s.top_ops[0][0] == want["top_op"]


def test_nested_kernel_reads_as_the_kernel_run_as_its_own_program():
    """Each bench.step of the chip fixture taken as the window: the kernel
    as its own program, then nested in a larger jax.jit."""
    path = os.path.join(HERE, "trace_nested_chip.json")
    want = _expected("trace_nested_chip.json")
    per_step = []
    for i in range(2):
        p = _profile(path)
        host = p.planes[0].lines[0]
        steps = [e for e in host.events if e.name == "bench.step"]
        host.events = [e for e in host.events if e.name != "bench.window"]
        host.events.append(_Obj(name="bench.window",
                                start_ns=steps[i].start_ns,
                                duration_ns=steps[i].duration_ns))
        per_step.append(devtrace.reduce(p).kernels)
    top, nested = per_step
    assert set(top) == set(nested) == set(want["kernels"])
    for k in top:
        assert nested[k] == pytest.approx(top[k], rel=0.05)
    assert [top, nested] == pytest.approx(want["kernels_by_step"])


def test_no_window_or_no_device_reads_nothing():
    path = os.path.join(HERE, "trace_small.json")
    p = _profile(path)
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert devtrace.reduce(p) is None
    p = _profile(path)
    for pl in p.planes:
        for ln in pl.lines:
            ln.events = [e for e in ln.events if e.name != "bench.window"]
    assert devtrace.reduce(p) is None


def test_program_names():
    assert devtrace.program_name("jit_camp_gemm_fused_w8a8(123)") == \
        "camp_gemm_fused_w8a8"
    assert devtrace.program_name("jit__paged_attention_pallas(7)") == \
        "_paged_attention_pallas"


KERNEL_OP = ('%_paged_attention_pallas.1 = bf16[8,2,7,64]{3,2,1,0} '
             'custom-call(s32[8,16]{1,0} %t, bf16[8,2,7,64]{3,2,1,0} %q), '
             'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name,kind,kernel", [
    (KERNEL_OP, "_paged_attention_pallas", "_paged_attention_pallas"),
    # the same instruction nested in a larger program reads the same
    (KERNEL_OP.replace(".1 =", ".7 ="), "_paged_attention_pallas",
     "_paged_attention_pallas"),
    ("%copy.3 = s8[6250,2,128,64]{3,2,1,0} copy(s8[6250,2,128,64]{2,3,1,0} "
     "%k_pages.1)", "copy", None),
    ("%custom-call.1 = f32[6250,2,128]{2,1,0} custom-call(f32[1564,2,128]"
     "{2,1,0} %s), custom_call_target=\"ConcatBitcast\"", "custom-call",
     None),
    ("%broadcast_multiply_fusion = bf16[8,896]{1,0} fusion(bf16[8,896]{1,0} "
     "%x), kind=kLoop", "broadcast_multiply_fusion", None),
    ("scatter.2", "scatter", None),
], ids=["kernel", "kernel_nested", "layout_copy", "other_custom_call",
        "fusion", "bare_name"])
def test_kernel_and_kind_of_an_op(name, kind, kernel):
    assert devtrace.op_kind(name) == kind
    assert devtrace.kernel_of(name) == kernel
