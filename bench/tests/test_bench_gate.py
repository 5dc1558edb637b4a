"""The kernel gate: every kernel on the served path resolved to compiled
Pallas and was traced into a program, its own or a larger one."""
import jax
import jax.numpy as jnp
import pytest

import tiny  # noqa: F401
import harness

PALLAS = {"fused_gemm": "pallas", "paged": "pallas", "interpret": False}
KERNELS = ("camp_gemm_fused_w8a8", "_paged_attention_pallas",
           "_paged_prefill_pallas")


@pytest.fixture
def compiled_pallas(monkeypatch):
    """The served path resolved as on the chip."""
    monkeypatch.setattr(harness, "kernel_resolution", lambda: PALLAS)


def _named_jit(name):
    def f(x):
        return x * 2.0
    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def _trace_nested(names):
    """Each of ``names`` a jitted function called only inside an outer
    ``jax.jit``, as a compiled engine step would call a kernel. Returns the
    outer function and the inner ones."""
    inner = [_named_jit(n) for n in names]

    @jax.jit
    def step(x):
        for f in inner:
            x = f(x)
        return x + 1.0
    jax.block_until_ready(step(jnp.ones(4)))
    return step, inner


def test_kernel_called_only_inside_another_jit_is_counted(compiled_pallas):
    traces = harness.KernelTraces()
    _, inner = _trace_nested(KERNELS)
    # the count of top-level programs misses them
    assert all(f._cache_size() == 0 for f in inner)
    counts = harness.assert_compiled_kernels("w8a8", traces)
    assert set(counts) == set(KERNELS)
    assert all(v >= 1 for v in counts.values())


def test_second_run_in_one_process_passes_on_the_shared_count(
        compiled_pallas):
    """A second run in the process (the knee sweep, the limits' loop over
    seeds) reuses the programs the first traced: JAX serves them from its
    trace cache and reports no trace. The process's one counter still
    holds the first run's counts."""
    traces = harness.kernel_traces()
    assert harness.kernel_traces() is traces
    step, _ = _trace_nested(KERNELS)
    first = harness.assert_compiled_kernels("w8a8", harness.kernel_traces())
    fresh = harness.KernelTraces()
    jax.block_until_ready(step(jnp.ones(4)))
    assert all(fresh.counts.get(k, 0) == 0 for k in KERNELS)
    second = harness.assert_compiled_kernels("w8a8", harness.kernel_traces())
    assert second == first and all(v >= 1 for v in second.values())


@pytest.mark.parametrize("missing", KERNELS)
def test_kernel_resolved_but_never_traced_raises(missing, compiled_pallas):
    traces = harness.KernelTraces()
    _trace_nested([k for k in KERNELS if k != missing])
    with pytest.raises(RuntimeError, match="never compiled on the path") as e:
        harness.assert_compiled_kernels("w8a8", traces)
    assert missing in str(e.value)


@pytest.mark.parametrize("resolved", [
    dict(PALLAS, fused_gemm="xla"),
    dict(PALLAS, paged="xla"),
    dict(PALLAS, interpret=True),
], ids=["gemm_resolved_to_xla", "paged_resolved_to_xla", "interpret_mode"])
def test_kernel_not_compiled_pallas_raises(resolved, monkeypatch):
    monkeypatch.setattr(harness, "kernel_resolution", lambda: resolved)
    traces = harness.KernelTraces()
    _trace_nested(KERNELS)
    with pytest.raises(RuntimeError, match="not compiled Pallas"):
        harness.assert_compiled_kernels("w8a8", traces)


def test_this_machine_resolves_to_xla_and_the_gate_raises():
    # on the CPU the served path resolves to the XLA reference
    with pytest.raises(RuntimeError, match="not compiled Pallas"):
        harness.assert_compiled_kernels("w8a8", harness.KernelTraces())
