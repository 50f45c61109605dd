"""Dynamic native custom-op libraries (lib_api.h / MXLoadLib analog)."""
import shutil

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import _native, nd
from incubator_mxnet_tpu.ops import registry as reg


@pytest.fixture(scope="module")
def loaded():
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no make/g++ to build the sample lib from source")
    return mx.library.load(_native.build("libsample_custom_op.so"),
                           verbose=False)


def test_load_registers_ops(loaded):
    assert set(loaded) == {"my_gelu", "my_weighted_add"}
    assert "my_gelu" in reg.OPS


def test_custom_op_eager(loaded):
    x = np.linspace(-3, 3, 16).astype(np.float32)
    out = reg.invoke("my_gelu", [nd.array(x)])
    expect = 0.5 * x * (1 + np.tanh(0.7978845608 * (x + 0.044715 * x ** 3)))
    np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-5, atol=1e-6)

    a = np.ones(8, np.float32)
    b = np.full(8, 2.0, np.float32)
    out2 = reg.invoke("my_weighted_add", [nd.array(a), nd.array(b)])
    np.testing.assert_allclose(out2.asnumpy(), 0.75 * a + 0.25 * b)


def test_custom_op_inside_jit(loaded):
    """pure_callback makes the native op usable inside compiled programs —
    the host-callback analog of the reference's CPU custom-op engine push."""
    import jax
    import jax.numpy as jnp

    op = reg.get_op("my_gelu")

    @jax.jit
    def f(x):
        return op.fn(x) * 2.0

    x = jnp.linspace(-1, 1, 8, dtype=jnp.float32)
    got = np.asarray(f(x))
    expect = 2 * 0.5 * np.asarray(x) * (
        1 + np.tanh(0.7978845608 * (np.asarray(x)
                                    + 0.044715 * np.asarray(x) ** 3)))
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
