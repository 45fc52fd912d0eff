"""Hooks for the whole test run: ``tests/`` and ``benchmarks/chip/tests``.

The chip harness's tests time host work in windows of tens of
milliseconds, a full garbage collection among it. A collection walks every
live object of the process, and a pytest process (a worker under xdist, or
the one process of a serial run) still holds what the tests before it
imported and built: after ``tests/test_text.py`` alone a full collection
can take 90 ms, longer than such a window. So each of those tests runs with
the heap it inherited frozen (``gc.freeze``), and collections see only what
the test itself makes, as in a process of its own.
"""

from __future__ import annotations

import gc
from pathlib import Path

import pytest

_CHIP_TESTS = Path(__file__).resolve().parent / "benchmarks" / "chip" / "tests"


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if _CHIP_TESTS not in Path(item.path).resolve().parents:
        yield
        return
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()
