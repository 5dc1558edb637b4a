"""CPU rehearsal of ``chip_smoke.py``: its phases' control flow at the
reduced qwen2-0.5b config (Pallas kernels in interpret mode, the XLA
reference for ``impl='auto'``), and its refusal to run without a TPU."""
import importlib.util
import os

import pytest

from repro.configs import get_config

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

CFG = get_config("qwen2-0.5b", reduced=True)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_serving_phase_on_cpu():
    out = chip_smoke.serve_smoke(CFG, seed=0, log=lambda m: None)
    assert out["requests"] == 5
    assert out["tokens"] == 4 * 8 + 16
    assert out["logits_rel_rms"] <= chip_smoke.LOGITS_TOL
    assert out["shared_pages"] >= 1
    assert out["spec"]["spec_steps"] > 0
    assert out["resolved"] == {"fused_gemm": "xla", "paged_decode": "xla",
                               "paged_prefill": "xla", "interpret": True}


@pytest.mark.parametrize("kind", ["gemm", "paged"])
def test_kernel_parity_on_cpu(kind):
    errs = chip_smoke.kernel_parity(CFG, seed=0)
    checked = {n: v for n, v in errs.items() if n.startswith(kind)}
    assert checked
    for name, (err, tol) in checked.items():
        assert err <= tol, (name, err)
