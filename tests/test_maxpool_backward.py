"""The gradient of ``Pooling(pool_type="max")`` routes the WHOLE gradient of
a window to its FIRST maximum in row-major scan order (the reference's
``pool.h unpool_max_*``).  The oracle is a NumPy loop over windows, written
from that rule and from nothing in ``ops/nn.py``; the inputs are full of ties
(post-ReLU zeros, and equal positives), where routing to every tie, or to the
last one, would show."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import autograd, nd

#: name -> (input shape, kernel, stride, pad, pooling_convention)
GEOMETRIES = {
    "vgg_2x2s2": ((2, 3, 8, 8), (2, 2), (2, 2), (0, 0), "valid"),
    "resnet_stem_3x3s2p1": ((2, 3, 10, 10), (3, 3), (2, 2), (1, 1), "valid"),
    "3x3s1p1": ((2, 3, 7, 7), (3, 3), (1, 1), (1, 1), "valid"),
    "full_2x2s2_odd": ((2, 3, 7, 9), (2, 2), (2, 2), (0, 0), "full"),
    "1d_3s2p1": ((2, 3, 11), (3,), (2,), (1,), "valid"),
    "3d_2x2x2s2": ((2, 2, 4, 6, 4), (2, 2, 2), (2, 2, 2), (0, 0, 0), "valid"),
}


def unpool_max(x, g, kernel, stride, pad, convention):
    """``(pooled, dx)`` by the reference's rule, one window at a time, in
    float64 (exact for the values below).  ``full`` rounds the output size
    up, its last windows hanging over the high edge."""
    hi = [p + s - 1 if convention == "full" else p
          for p, s in zip(pad, stride)]
    padded = np.full(x.shape[:2] + tuple(
        n + p + h for n, p, h in zip(x.shape[2:], pad, hi)), -np.inf)
    inside = (slice(None),) * 2 + tuple(
        slice(p, p + n) for p, n in zip(pad, x.shape[2:]))
    padded[inside] = x
    out_shape = tuple((n - k) // s + 1 for n, k, s in
                      zip(padded.shape[2:], kernel, stride))
    pooled = np.zeros(x.shape[:2] + out_shape)
    dpadded = np.zeros(padded.shape)
    for b, c in itertools.product(range(x.shape[0]), range(x.shape[1])):
        for w in itertools.product(*[range(n) for n in out_shape]):
            best, where = -np.inf, None
            for off in itertools.product(*[range(k) for k in kernel]):
                at = tuple(wi * s + o for wi, s, o in zip(w, stride, off))
                if where is None or padded[(b, c) + at] > best:
                    best, where = padded[(b, c) + at], at
            pooled[(b, c) + w] = best
            dpadded[(b, c) + where] += g[(b, c) + w]
    return pooled, dpadded[inside]


def _tied_input(rng, shape):
    """Half zeros (post-ReLU), the rest from four positive values."""
    return np.maximum(rng.randint(-4, 5, shape), 0) * 0.5


@pytest.mark.parametrize("cotangent", ["dyadic", "random"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_max_pool_gradient_goes_to_the_first_maximum(geometry, dtype,
                                                     cotangent):
    shape, kernel, stride, pad, convention = GEOMETRIES[geometry]
    rng = np.random.RandomState(len(geometry))
    x_np = _tied_input(rng, shape)
    x = nd.array(x_np, dtype=dtype)
    x.attach_grad()
    with autograd.record():
        y = nd.Pooling(x, kernel=kernel, stride=stride, pad=pad,
                       pool_type="max", pooling_convention=convention)
    if cotangent == "dyadic":
        # small integers: every sum over overlapping windows (at most 9
        # terms) is exact in bfloat16, so the order of summation cannot show
        g_np = rng.randint(-3, 4, y.shape).astype(np.float64)
    else:
        # positive, so that no sum cancels and the support is the routing
        g_np = np.asarray(jnp.asarray(rng.uniform(0.5, 1.5, y.shape),
                                      dtype), np.float64)
    y.backward(nd.array(g_np, dtype=dtype))
    pooled, want = unpool_max(x_np, g_np, kernel, stride, pad, convention)
    got = np.asarray(x.grad._data, np.float64)
    assert x.grad.dtype == x.dtype and got.shape == x_np.shape
    np.testing.assert_array_equal(np.asarray(y._data, np.float64), pooled)
    if cotangent == "dyadic":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_array_equal(got != 0, want != 0)
        # a position collects from at most prod(kernel) windows
        np.testing.assert_allclose(
            got, want, rtol=np.prod(kernel) * float(jnp.finfo(dtype).eps))
