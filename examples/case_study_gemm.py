"""Paper §4.2 case study, runnable: compare two GEMM implementations
through ScALPEL counters with call-count multiplexing.

    PYTHONPATH=src python examples/case_study_gemm.py
"""
import sys

sys.path.insert(0, ".")

from benchmarks import case_study  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


if __name__ == "__main__":
    enable_compile_cache()
    case_study.main(fast=True)
