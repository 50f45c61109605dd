"""Fixtures of the benchmark's own tests."""
import importlib.util
import os
import sys

import pytest

_TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "bench", "runners", "toy_tokens.py")


@pytest.fixture
def toy_tokens_runner(monkeypatch):
    """``perfbench.runners.toy_tokens`` for the length of one test: the
    runner of the cell ``tiny_tokens`` of ``data/BENCHMARK.json``, which is
    no file of ``perfbench/runners/``."""
    name = "perfbench.runners.toy_tokens"
    spec = importlib.util.spec_from_file_location(name, _TOY)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, name, module)
    return module
