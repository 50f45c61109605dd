"""Tool wiring.

* ``bench.py --schedule-config``: the autotune winner loader fails
  fast (before the ResNet build) on a malformed config.
"""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_cli(name, path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_schedule_config_rejects_malformed(tmp_path):
    bench = _load_cli("bench_cli", "bench.py")
    bad = tmp_path / "winner.json"
    bad.write_text(json.dumps({"target": "train-schedule", "knobs": {}}))
    # the loader runs BEFORE the ResNet build: a malformed winner config
    # costs an exception, not a model build + trace
    with pytest.raises(ValueError, match="schedule"):
        bench.run_train(schedule_config=str(bad))
