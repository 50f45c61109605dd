"""Test harness config.

Mirrors the reference strategy (SURVEY.md §4): run the suite on the XLA-CPU
backend with a virtual 8-device mesh so multi-chip sharding tests run without
TPU hardware (the reference's analog: fake-ctx consistency checks +
multi-process kvstore tests on one host).

The suite never needs the chip: ``pin_cpu`` sets ``JAX_PLATFORMS=cpu`` and
the virtual device count before jax is imported.  What has to be asked of
the chip's compiler is asked of a DESCRIBED chip, inside a fixture of
``tests/test_chip_compile.py``; the chip itself is reached by
``python chip_smoke.py`` through the builder's tool.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _platform_pin import pin_cpu

jax = pin_cpu(8)
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_rng():
    """with_seed() analog: deterministic seeds per test (common.py:161).

    MXNET_TEST_SEED overrides the default — tools/flakiness_checker.py
    reruns suites across seeds through this hook, exactly like the
    reference's with_seed() env override."""
    import incubator_mxnet_tpu as mx

    seed = int(os.environ.get("MXNET_TEST_SEED", "42"))
    mx.random.seed(seed)
    np.random.seed(seed)
    yield
