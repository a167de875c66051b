"""Shared by the benchmark's tests: the repo root on sys.path, and a
registry that also sees the tiny cells under tests/benchmark/data."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(ROOT, "tests", "benchmark", "data")


def tiny_registry():
    from benchmark.cells import Registry
    return Registry(ROOT, extra=[TINY])
