"""The rotary embedding op, ``op._contrib_rotary`` (``ops/decoder.py``): one
Pallas pass each way (``rotary``, ``rotary_bwd``) where the head size fills
whole vregs of 128 lanes, XLA's own ops elsewhere.

The kernel is held to TODAY'S form, kept here as the reference
(``_reference``: ``x * cos + [-x2, x1] * sin`` in float32, the result in
the input's dtype), bit for bit, forward and ``jax.vjp``, up to a zero's
sign.  Those comparisons run in ONE child process whose XLA:CPU may not use
fused multiply-adds (``--xla_cpu_max_isa=AVX``): the host's LLVM contracts
``a * b + c * d`` into an FMA where it sees fit, and it sees fit
differently in the reference and in the interpreted kernel (6,305 of
24,576 float32 elements an ulp apart, forward), where the chip
rounds each product (0 elements differ there at the cells' shapes:
PERF.md section 6, PR 41).  The child is started once for the module, and
each case reads its own line of what it printed.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (shape, dtype, kernel expected, under ``jax.checkpoint``)
CASES = {
    "d128_1_head_bf16": ((1, 1, 64, 128), "bfloat16", True, False),
    "d128_4_heads_bf16": ((2, 4, 40, 128), "bfloat16", True, False),
    "d128_28_heads_f32": ((1, 28, 16, 128), "float32", True, False),
    "d128_rows_past_a_block_bf16": ((1, 2, 4100, 128), "bfloat16", True,
                                    False),
    "d256_4_heads_bf16": ((1, 4, 24, 256), "bfloat16", True, False),
    "d128_checkpoint_bf16": ((1, 4, 32, 128), "bfloat16", True, True),
    "d128_checkpoint_f32": ((1, 4, 32, 128), "float32", True, True),
    "d64_xla_bf16": ((1, 4, 48, 64), "bfloat16", False, False),
    "d64_checkpoint_xla_f32": ((1, 4, 32, 64), "float32", False, True),
}


def _reference(data, theta):
    """Today's form of the op, before the kernel (PR 40's ``_rotary``)."""
    import jax.numpy as jnp

    s, d = data.shape[-2:]
    inv = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, -1).astype(np.float32)
    sin = np.concatenate([np.sin(ang)] * 2, -1).astype(np.float32)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    out = x * cos + jnp.concatenate([-x2, x1], -1) * sin
    return out.astype(data.dtype)


def _differ(a, b):
    """(elements whose bits differ, of them those that are zeros of either
    sign on both sides)."""
    import jax.numpy as jnp

    a = np.asarray(jnp.asarray(a).astype(jnp.float32)).view(np.uint32)
    b = np.asarray(jnp.asarray(b).astype(jnp.float32)).view(np.uint32)
    differ = a != b
    return int(differ.sum()), int((differ & ((a | b) & 0x7FFFFFFF == 0)).sum())


def _child():
    """Every case, kernel against ``_reference``; one JSON line a case."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import decoder

    theta = 10000.0
    for name, (shape, dtype, _, checkpointed) in CASES.items():
        keys = jax.random.split(jax.random.key(41), 2)
        x = jax.random.normal(keys[0], shape, dtype)
        g = jax.random.normal(keys[1], shape, dtype)
        got, want = [], []
        for op, out in ((decoder._rotary, got), (_reference, want)):
            def f(x, op=op):
                return op(x, theta)
            if checkpointed:
                f = jax.checkpoint(f)
            y, pull = jax.vjp(f, x)
            out += [y, pull(g)[0]]
        print(json.dumps({
            "case": name, "kernel": _kernels(jax.make_jaxpr(
                lambda x: decoder._rotary(x, theta))(x)) == ["rotary"],
            "fwd": _differ(got[0], want[0]), "bwd": _differ(got[1], want[1]),
            "finite": bool(np.isfinite(np.asarray(
                got[1].astype(jnp.float32))).all())}), flush=True)


@pytest.fixture(scope="module")
def compared():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=AVX").strip())
    run = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-4000:]
    return {d["case"]: d for d in map(json.loads, run.stdout.splitlines())}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_todays_op_bit_for_bit(compared, case):
    """Forward and gradient the reference's bits, but for a zero's sign; the
    kernel runs where the case expects it."""
    got = compared[case]
    assert got["kernel"] is CASES[case][2]
    for part in ("fwd", "bwd"):
        differ, zeros = got[part]
        assert differ == zeros, (part, got)
    assert got["finite"]


def _kernels(jaxpr):
    import re

    return sorted(set(re.findall(r"name=(rotary\w*)", str(jaxpr))))


@pytest.mark.parametrize("shape,dtype,kernels", [
    ((1, 4, 48, 128), "bfloat16", ["rotary", "rotary_bwd"]),
    ((2, 3, 40, 256), "float32", ["rotary", "rotary_bwd"]),
    ((1, 20, 48, 64), "bfloat16", []),
    ((1, 4, 48, 192), "bfloat16", []),
    ((1, 4, 48, 128), "float16", []),
    ((1, 4, 48, 128), "float64", []),
], ids=["d128", "d256", "d64", "d192", "float16", "float64"])
def test_the_shape_and_dtype_choose_the_form(shape, dtype, kernels):
    """One forward and one backward kernel where the head size is a
    multiple of 128, in bfloat16 or float32; XLA's ops elsewhere, and there
    the same values as the reference."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import decoder

    x = jnp.ones(shape, dtype)
    jaxpr = jax.make_jaxpr(lambda x: jax.vjp(
        lambda x: decoder._rotary(x, 100.0), x)[1](x))(x)
    assert _kernels(jaxpr) == kernels
    if not kernels:
        x = jax.random.normal(jax.random.key(0), shape, jnp.float32).astype(
            dtype)
        assert _differ(decoder._rotary(x, 100.0),
                       _reference(x, 100.0)) == (0, 0)


def test_call_sites_share_one_lowered_kernel():
    """Four call sites of one shape (q and k, two layers) lower ONE function
    that holds the kernel, called four times, each way
    (``_backend.lowered_once``): a ``pallas_call`` traced and lowered at
    every site costs a tenth of a second each."""
    import re

    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import decoder

    def loss(x):
        for _ in range(4):
            x = decoder._rotary(x, 500.0)
        return x.astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(
        jnp.ones((1, 2, 16, 128), jnp.bfloat16)).as_text()
    for name in ("rotary", "rotary_bwd"):
        assert len(re.findall(r"func\.func private @%s\(" % name, text)) == 1
        assert len(re.findall(r"call @%s\(" % name, text)) == 4


if __name__ == "__main__":
    _child()
