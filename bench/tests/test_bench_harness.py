"""The harness end to end at a tiny size on the CPU, steered from here."""
import pytest

import tiny
import harness


def test_tiny_run_reports_end_to_end_metrics_and_is_correct(tmp_path):
    out = tiny.run(str(tmp_path))
    assert out["correct"] is True, out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 1
    assert list(out)[-1] == "checked"
    assert set(out["checked"]) == {"max_logit_gap", "compared_tokens"}


def test_traced_run_reads_per_layer_metrics_from_files(tmp_path):
    out = tiny.run(str(tmp_path), traced=True)
    assert out["correct"] is True, out["checked"]
    # window_steps is a metric the tiny checkout added as a file alone; the
    # trace's device metrics find no TPU plane on the CPU and stay out
    assert out["metrics"]["window_steps"]["value"] > 0
    assert {"step_ms_p50", "prefix_hit_share",
            "compiles_in_window"} <= set(out["metrics"])
    assert "camp_gemm_roofline" not in out["metrics"]
    assert "ttft_p50_ms" not in out["metrics"]


def test_control_at_lower_weight_precision_is_not_correct(tmp_path):
    # int4 weights where the configuration states int8: the control
    out = tiny.run(str(tmp_path), qmode=tiny.MODEL["control"]["weights"])
    assert out["correct"] is False, out["checked"]


def test_warm_up_passes_every_batch_size_the_window_reaches(tmp_path):
    out, run = tiny.run(str(tmp_path), full=True)
    window = max(len(s.decode_lengths) for s in run.window_steps())
    assert 0 < window <= run.counters["warm_up_batch"]
    # every twin is due in the window, and all of them decode together once
    due = sum(1 for r in run.served if r.due < run.seconds)
    assert run.counters["warm_up_batch"] == due


class _Pool:
    page_size = 128


class _Engine:
    pool = _Pool()
    chunk_tokens = 512


@pytest.mark.parametrize("prompts,prefixes,first", [
    # a first prompt of a system prompt takes three 512-token chunks, one
    # that finds its 1024 tokens in the trie one chunk, unless its user
    # turn is longer than a chunk
    ([1100, 1150, 1200], [0, 0, 1], [3, 4, 7]),
    ([1100, 1700, 1064], [-1, 0, 0], [3, 7, 8]),
])
def test_warm_up_twins_end_in_the_last_twins_first_step(prompts, prefixes,
                                                         first):
    import traffic
    twins = [traffic.Request(j, 0.5 * j, [0] * n, 100, p,
                             1024 if p >= 0 else 0)
             for j, (n, p) in enumerate(zip(prompts, prefixes))]
    got = harness.warm_up_requests(twins, _Engine())
    assert all(r.due_s == 0.0 for r in got)
    # twin i has its first token in step first[i] and decodes in that step;
    # it ends once it holds max_new tokens, in the step of the last first
    assert [f + r.max_new - 2 for f, r in zip(first, got)] == \
        [first[-1]] * len(got)
    assert [len(r.prompt) for r in got] == prompts
