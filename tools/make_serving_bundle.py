#!/usr/bin/env python
"""Self-contained serving bundle (the amalgamation analog).

The reference's ``amalgamation/`` squashes a predict-only runtime into a
single C++ file so a model can be served with no MXNet checkout.  The
TPU-native runtime is Python/JAX, so the equivalent deliverable is a
directory that serves a saved model with NOTHING from the repo on the
path:

    bundle/
      libmxtpu_capi.so      the C ABI (MXPred* serving surface)
      incubator_mxnet_tpu/  the runtime package (pruned: no tests)
      model-symbol.json     the model graph
      model-0000.params     the weights
      serve.py              minimal example consumer (ctypes, MXPred*)
      README.md             how to run from anywhere

Usage:
    python tools/make_serving_bundle.py <model_prefix> <outdir> \
        [input_shape_json]          # e.g. '[1, 3, 224, 224]' 

Verify (from any cwd, repo not on path):
    cd <outdir> && python serve.py
"""
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SERVE = '''#!/usr/bin/env python
"""Minimal MXPred* consumer running entirely out of this bundle."""
import ctypes
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                  # bundled runtime package
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

lib = ctypes.CDLL(os.path.join(HERE, "libmxtpu_capi.so"))
lib.MXGetLastError.restype = ctypes.c_char_p


def check(rc):
    assert rc == 0, lib.MXGetLastError().decode()


symbol_json = open(os.path.join(HERE, "model-symbol.json")).read()
params = open(os.path.join(HERE, "model-0000.params"), "rb").read()
shape = json.loads(os.environ.get("INPUT_SHAPE", "__DEFAULT_SHAPE__"))

h = ctypes.c_void_p()
indptr = (ctypes.c_uint32 * 2)(0, len(shape))
sdata = (ctypes.c_uint32 * len(shape))(*shape)
keys = (ctypes.c_char_p * 1)(b"data")
check(lib.MXPredCreate(symbol_json.encode(), params, len(params), 1, 0,
                       1, keys, indptr, sdata, ctypes.byref(h)))
x = np.random.RandomState(0).uniform(size=shape).astype(np.float32)
check(lib.MXPredSetInput(h, b"data", x.ctypes.data_as(
    ctypes.POINTER(ctypes.c_float)), x.size))
check(lib.MXPredForward(h))
pshape = ctypes.POINTER(ctypes.c_uint32)()
ndim = ctypes.c_uint32()
check(lib.MXPredGetOutputShape(h, 0, ctypes.byref(pshape),
                               ctypes.byref(ndim)))
oshape = [pshape[i] for i in range(ndim.value)]
out = np.zeros(int(np.prod(oshape)), np.float32)
check(lib.MXPredGetOutput(h, 0, out.ctypes.data_as(
    ctypes.POINTER(ctypes.c_float)), out.size))
check(lib.MXPredFree(h))
print("output shape:", oshape)
print("output[:5]:", out[:5])
print("SERVE OK")
'''

_README = '''# Serving bundle

Self-contained predict-only artifact (the reference `amalgamation/`
analog): everything needed to serve `model-symbol.json` +
`model-0000.params` through the MXPred* C ABI lives in this directory.

Run the bundled example consumer (CPU):

    python serve.py

Embed in your own process: load `libmxtpu_capi.so`, use the MXPred*
functions declared in the reference `c_predict_api.h` contract.  The
.so embeds CPython and imports the bundled `incubator_mxnet_tpu/`
package from this directory (set PYTHONPATH here when embedding from
C/C++).
'''


def main():
    if len(sys.argv) not in (3, 4):
        print(__doc__)
        return 1
    prefix, outdir = sys.argv[1], sys.argv[2]
    default_shape = sys.argv[3] if len(sys.argv) == 4 else "[1, 3, 224, 224]"
    os.makedirs(outdir, exist_ok=True)
    # no binary is committed: each library is built from src/native here
    sys.path.insert(0, REPO)
    from incubator_mxnet_tpu import _native

    for native in ("libmxtpu_capi.so", "libmxtpu_native.so",
                   "libsample_custom_op.so"):
        shutil.copy2(_native.build(native), outdir)
    shutil.copy2(prefix + "-symbol.json",
                 os.path.join(outdir, "model-symbol.json"))
    shutil.copy2(prefix + "-0000.params",
                 os.path.join(outdir, "model-0000.params"))
    pkg_dst = os.path.join(outdir, "incubator_mxnet_tpu")
    if os.path.exists(pkg_dst):
        shutil.rmtree(pkg_dst)
    shutil.copytree(os.path.join(REPO, "incubator_mxnet_tpu"), pkg_dst,
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    with open(os.path.join(outdir, "serve.py"), "w") as f:
        f.write(_SERVE.replace("__DEFAULT_SHAPE__", default_shape))
    with open(os.path.join(outdir, "README.md"), "w") as f:
        f.write(_README)
    size = sum(os.path.getsize(os.path.join(dp, fn))
               for dp, _, fns in os.walk(outdir) for fn in fns)
    print("bundle at %s (%.1f MB)" % (outdir, size / 1e6))
    return 0


if __name__ == "__main__":
    sys.exit(main())
