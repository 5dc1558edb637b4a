"""Without a TPU, or without the program beside it, a run exits non-zero
and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import tiny

CMD = ["bench/run.py", "--workload", "qwen2-0.5b.chat-sysprompt",
       "--seed", "2147483653", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable] + CMD, cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_no_tpu_no_result():
    rc, out = _run(tiny.ROOT)
    assert rc != 0 and not _has_result(out)


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    rc, out = _run(str(tmp_path))
    assert rc != 0 and not _has_result(out)
