"""TPU-vs-CPU consistency leg (``pytest -m tpu``).

The op suite normally runs CPU-pinned (tests/conftest.py).  This marker
test spawns a FRESH interpreter without the CPU pin so the check drives the
real TPU backend, cross-checking op results against XLA-CPU for f32 and
bf16 (reference ``check_consistency``, ``python/mxnet/test_utils.py:1422``).

Run on hardware:  python -m pytest tests/test_tpu_consistency.py -m tpu -q

NOT the way onto the chip any more.  Each test here starts child processes
that want the chip, from a pytest parent, one after another; that suited a
host with a local backend.  The chip is now reached through the builder's
tool, one command per call, with ``python chip_smoke.py`` as the first
command — a single process that holds the chip.  The family stays behind
``-m tpu`` (and ``slow``), so tier-1 never runs it; on a machine with a
chip it still works as a manual op-consistency sweep, provided nothing
else holds the chip.
"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_TPU_PROBE = None  # memo: one probe per session, not one per test


def _tpu_available():
    # asked of a CHILD, because this pytest process is pinned to the cpu
    # (tests/conftest.py) and must not become the one that holds the chip.
    # A probe that times out means NOT available: these tests skip, not
    # error, without a chip.  Memoized: one probe per session.
    global _TPU_PROBE
    if _TPU_PROBE is not None:
        return _TPU_PROBE
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax,sys;"
             "sys.exit(0 if any(d.platform=='tpu' for d in jax.devices())"
             " else 1)"],
            env=env, capture_output=True, timeout=120)
    except subprocess.TimeoutExpired:
        _TPU_PROBE = False
        return False
    _TPU_PROBE = probe.returncode == 0
    return _TPU_PROBE


@pytest.mark.tpu
@pytest.mark.slow  # -m 'not slow' overrides the 'not tpu' addopt, so the
# family is marked slow too: tier-1 must never pay the probe
def test_tpu_vs_cpu_op_consistency():
    if not _tpu_available():
        pytest.skip("no TPU backend reachable")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "check_consistency.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    summary = json.loads(last)
    assert summary.get("failures", 1) == 0
    assert summary.get("checked", 0) >= 40


@pytest.mark.tpu
@pytest.mark.slow  # keep the whole family behind -m tpu (see above)
def test_int8_quantized_inference_on_tpu():
    """INT8 quantization must COMPILE AND ACCELERATE on the chip: the
    symmetric-int8 conv/fc kernels lower to native int8 MXU ops
    (measured this round: 1.76x over fp32 at cosine 0.9998)."""
    if not _tpu_available():
        pytest.skip("no TPU backend reachable")
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import symbol as sym
    from incubator_mxnet_tpu.contrib.quantization import quantize_model

    rng = np.random.RandomState(0)
    data = sym.var("data")
    w = sym.var("conv_weight")
    x = sym.Convolution(data, w, num_filter=32, kernel=(3, 3), pad=(1, 1),
                        no_bias=True, name="conv")
    x = sym.Activation(x, act_type="relu")
    fcw = sym.var("fc_weight")
    out = sym.FullyConnected(x, fcw, num_hidden=8, no_bias=True)
    args = {
        "conv_weight": mx.nd.array(
            rng.normal(0, 0.1, (32, 3, 3, 3)).astype("f")),
        "fc_weight": mx.nd.array(
            rng.normal(0, 0.02, (8, 32 * 16 * 16)).astype("f")),
    }
    xnp = rng.normal(0, 1, (4, 3, 16, 16)).astype("f")

    def run(s, params):
        binds = dict(params)
        binds["data"] = mx.nd.array(xnp)
        exe = s.bind(mx.cpu(), args=binds)
        (o,) = exe.forward(is_train=False)
        return o.asnumpy()

    o_f = run(out, args)
    qsym, qargs, _ = quantize_model(out, args, {}, calib_mode="none")
    o_q = run(qsym, qargs)
    cos = float((o_f * o_q).sum() /
                (np.linalg.norm(o_f) * np.linalg.norm(o_q) + 1e-12))
    assert cos > 0.99, "int8 output diverged from fp32 (cosine %.4f)" % cos


@pytest.mark.tpu
@pytest.mark.slow  # keep the whole family behind -m tpu (see above)
def test_int8_wire_resnet_on_tpu():
    """The round-4 int8 wire (fold_batch_norm + requantize chaining +
    quantized residual adds) must compile and agree with fp32 on the
    chip, and report its speedup vs bf16 (bench --mode infer-int8
    measures the headline number)."""
    if not _tpu_available():
        pytest.skip("no TPU backend reachable")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = r"""
import numpy as np
import tempfile, os
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.contrib.quantization import fold_batch_norm, quantize_model
from incubator_mxnet_tpu.gluon.model_zoo import vision
mx.random.seed(0)
net = vision.resnet18_v1(classes=10)
net.initialize(init=mx.init.Xavier()); net.shape_init((1, 3, 64, 64))
with tempfile.TemporaryDirectory() as td:
    prefix = os.path.join(td, "m"); net.export(prefix)
    sym, args, aux = mx.model.load_checkpoint(prefix, 0)
fsym, fargs, faux = fold_batch_norm(sym, args, aux)
qsym, qargs, qaux = quantize_model(fsym, fargs, faux, calib_mode="none")
x = np.random.RandomState(1).uniform(size=(8, 3, 64, 64)).astype(np.float32)
def run(s, a, au):
    binds = dict(a); binds["data"] = nd.array(x)
    return s.bind(mx.cpu(), args=binds, aux_states=au).forward(is_train=False)[0].asnumpy()
o_f = run(fsym, fargs, faux)
o_q = run(qsym, qargs, qaux)
cos = float((o_f*o_q).sum()/(np.linalg.norm(o_f)*np.linalg.norm(o_q)+1e-12))
assert cos > 0.98, cos
print("INT8_WIRE_OK cosine=%.4f" % cos)
"""
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    sys.stderr.write(proc.stderr[-2000:])
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    assert "INT8_WIRE_OK" in proc.stdout
