"""Serving a configuration over a tensor-parallel mesh of the cell's chips.

The runs at degree 4 go through the real harness on four virtual CPU
devices, each in a subprocess of its own (the device count may not be set
in the test session), all started at once; the rest runs in process."""
import gc
import json
import os
import subprocess
import sys
import weakref

import pytest

import tiny
import harness
from counting import Shape

CASES = ("degree_4", "degree_1", "exchange_left_out")
# the tiny model at degree 1: the same weights, no tensor_parallel key
ONE_CHIP = dict(tiny.TP_MODEL, serving=tiny.MODEL["serving"])


def _case(case: str, tmp: str) -> dict:
    """One short run (the burst mix, no warm-up, a 2 s window) of the tiny
    model, with what the engine and its weights were placed on, and whether
    the engine was still alive when the reference started."""
    import jax
    seen = {"psum": 0}
    if case == "exchange_left_out":
        # the row-parallel all-reduce of wo and w_down returns the local
        # partial; it is the only psum the serving path makes
        from repro.models import modules

        def local_partial(y, axis, **_):
            seen["psum"] += 1
            return y
        jax.lax.psum = local_partial
        modules._row_parallel_fn.cache_clear()
    build, compare, made = harness.build_engine, harness.compare, []

    def spy(params, *args, **kw):
        eng = build(params, *args, **kw)
        made.append(weakref.ref(eng))
        leaves = jax.tree_util.tree_leaves(params)
        pages = eng.pool.k_pages[0].sharding
        seen.update(
            tp=eng.tp, pool_spec=repr(getattr(pages, "spec", None)),
            pool_devices=len(pages.device_set),
            weight_devices=sorted({len(x.sharding.device_set)
                                   for x in leaves}))
        return eng
    harness.build_engine = spy

    def reference(*args, **kw):
        gc.collect()
        seen["engine_alive_at_reference"] = made[0]() is not None
        return compare(*args, **kw)
    harness.compare = reference
    out = tiny.run(tmp, model=ONE_CHIP if case == "degree_1" else
                   tiny.TP_MODEL, mix=tiny.BURST, warmup=False,
                   chips=1 if case == "degree_1" else 4)
    return dict(seen, correct=out["correct"], checked=out["checked"])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    procs = {c: subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), c,
         str(tmp_path_factory.mktemp(c))], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for c in CASES}
    out = {}
    for c, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, f"{c}: exit {p.returncode}\n{stderr[-4000:]}"
        out[c] = json.loads(stdout.strip().splitlines()[-1])
    return out


def test_degree_4_is_sharded_over_four_chips_and_correct(runs):
    r = runs["degree_4"]
    assert r["tp"] == 4
    assert r["pool_spec"] == "PartitionSpec(None, 'model', None, None)"
    assert r["pool_devices"] == 4 and r["weight_devices"] == [4]
    assert r["correct"] is True, r["checked"]


def test_degree_1_of_the_same_model_is_correct(runs):
    r = runs["degree_1"]
    assert r["tp"] == 1 and r["pool_devices"] == 1
    assert r["weight_devices"] == [1]
    assert r["correct"] is True, r["checked"]


def test_exchange_between_chips_left_out_is_not_correct(runs):
    r = runs["exchange_left_out"]
    assert r["psum"] > 0, "the fault did not fire: no row-parallel reduce"
    assert r["tp"] == 4
    assert r["correct"] is False, r["checked"]
    gap = r["checked"]["max_logit_gap"]
    assert gap["value"] > gap["limit"], gap


@pytest.mark.parametrize("case", CASES)
def test_engine_is_freed_before_the_reference(runs, case):
    """The reference runs on the first chip, where a tensor-parallel engine
    keeps one chip's share of the weights and the pool."""
    assert runs[case]["engine_alive_at_reference"] is False


class _Stop(Exception):
    pass


def test_degree_1_passes_no_mesh(tmp_path, monkeypatch):
    """A configuration without ``serving.tensor_parallel`` draws its weights
    and builds its engine with no mesh argument at all."""
    from repro.launch import serve
    from repro.serving import engine
    calls = {}
    draw = serve.init_serving_params

    def weights(*args, **kw):
        calls["weights"] = (len(args), sorted(kw))
        return draw(*args, **kw)

    def eng(*args, **kw):
        calls["engine"] = sorted(kw)
        raise _Stop
    monkeypatch.setattr(serve, "init_serving_params", weights)
    monkeypatch.setattr(engine, "ContinuousBatchingEngine", eng)
    with pytest.raises(_Stop):
        tiny.run(str(tmp_path), warmup=False)
    assert calls["weights"] == (3, [])
    assert "mesh" not in calls["engine"]


@pytest.mark.parametrize("model,chips,why", [
    (tiny.TP_MODEL, 1, "is not the cell's 1 chips"),
    (dict(tiny.MODEL, serving=dict(tiny.MODEL["serving"],
                                   tensor_parallel=4)), 4,
     "does not divide the 2 kv heads"),
], ids=["degree_not_the_cells_chips", "degree_not_dividing_kv_heads"])
def test_degree_is_refused_before_weights_are_drawn(tmp_path, monkeypatch,
                                                    model, chips, why):
    from repro.launch import serve

    def weights(*args, **kw):
        raise AssertionError("weights drawn before the refusal")
    monkeypatch.setattr(serve, "init_serving_params", weights)
    with pytest.raises(ValueError, match=why):
        tiny.run(str(tmp_path), model=model, chips=chips)


def _mc(model):
    cell = harness.find_cell(harness.load_benchmark(tiny.ROOT),
                             "qwen2-0.5b.chat-sysprompt", tiny.ROOT)
    cell.model = model
    return harness.model_config(cell)


@pytest.mark.parametrize("model,tp,share", [
    # d 64, 8 heads of 8, 4 kv heads, d_ff 256, untied vocab 512
    (tiny.TP_MODEL, 4, Shape(2, 64, 2, 1, 8, 64, 128, 8, False)),
    # tied: the head is the embedding, whose rows shard
    (dict(tiny.TP_MODEL, tie_word_embeddings=True), 2,
     Shape(2, 64, 4, 2, 8, 128, 256, 8, True)),
    # d_ff 250 and vocab 510 do not divide by 4: the serve rules leave the
    # gate projection and the head whole on every chip
    (dict(tiny.TP_MODEL, intermediate_size=250, vocab_size=510), 4,
     Shape(2, 64, 2, 1, 8, 250, 510, 8, False)),
    # qwen2-72b at w4a8 over a v5e 2x2: d 8192, 64 heads of 128, 8 kv
    # heads, d_ff 29568, untied vocab 152064
    (dict(tiny.TP_MODEL, hidden_size=8192, intermediate_size=29568,
          num_attention_heads=64, num_key_value_heads=8,
          num_hidden_layers=80, vocab_size=152064,
          serving=dict(tiny.TP_MODEL["serving"], weights="w4a8")), 4,
     Shape(80, 8192, 16, 2, 128, 7392, 38016, 4, False)),
], ids=["untied", "tied", "indivisible_d_ff_and_vocab", "qwen2-72b"])
def test_count_shape_is_one_chips_share(model, tp, share):
    mc = _mc(model)
    assert harness.count_shape(mc, tp) == share
    whole = harness.count_shape(mc)
    assert (whole.n_heads, whole.n_kv_heads, whole.d_ff, whole.vocab) == (
        mc.n_heads, mc.n_kv_heads, mc.d_ff, mc.vocab_size)


def test_kernels_keep_their_names_under_a_degree_4_mesh():
    """The kernel gate and the rooflines find the Pallas kernels by the
    custom calls' names; a degree-4 decode and prefill, compiled for a
    described v5e 2x2, still name them so."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "kernel_names_tp.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu", ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    p = subprocess.run([sys.executable, script], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["decode"]["kernels"] == ["_paged_attention_pallas",
                                        "camp_gemm_fused_w4a8"]
    assert out["prefill"]["kernels"] == ["_paged_prefill_pallas",
                                         "camp_gemm_fused_w4a8"]
    assert out["decode"]["collectives"] == ["all-reduce"]


if __name__ == "__main__":
    print(json.dumps(_case(sys.argv[1], sys.argv[2])), flush=True)
