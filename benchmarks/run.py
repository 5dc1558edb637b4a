"""Benchmark harness entrypoint — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (blocking_locality, cnn_llm_layers, decode_serving,
                            fused_gemm, instruction_count, roofline,
                            table1_smm, table4_conv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sections = [
        ("Table 1 (SMM 512 speedups)", table1_smm.rows),
        ("Fig 1 (blocking locality)", blocking_locality.rows),
        ("Figs 12/13/14 + Table 3 (CNN/LLM layers)", cnn_llm_layers.rows),
        ("Table 4 (conv throughput)", table4_conv.rows),
        ("Fig 17 (instruction count)", instruction_count.rows),
        ("Roofline (dry-run artifacts)", roofline.rows),
        ("Fused quantize+GEMM (ISSUE 1)", fused_gemm.rows),
        ("Paged-KV decode serving (ISSUE 2)", decode_serving.rows),
    ]
    print("name,us_per_call,derived")
    ok = True
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            for row in fn():
                print(row)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"# SECTION FAILED: {title}")
            traceback.print_exc()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
