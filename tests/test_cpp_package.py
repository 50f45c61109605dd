"""L9 binding path: a pure C++ consumer of the C ABI (cpp-package/),
equivalent to the reference's cpp-package + predict-cpp example."""
import functools
import os
import shutil
import subprocess
import sys

import pytest

from incubator_mxnet_tpu import _native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DIR = os.path.join(_REPO, "cpp-package")


@pytest.fixture(scope="module")
def capi_so():
    """Every binding links libmxtpu_capi.so: build it from source through
    the one locked builder BEFORE a demo's own make looks for it (other
    xdist workers build the same file for tests/test_c_api.py)."""
    return _native.build("libmxtpu_capi.so")


@functools.lru_cache(maxsize=1)
def _site_packages():
    return subprocess.run(
        [sys.executable, "-c",
         "import site;print(site.getsitepackages()[0])"],
        capture_output=True, text=True).stdout.strip()


def _cpp_env():
    """Environment for building/running the demos: cpu-pinned jax and a
    PYTHONPATH that lets the embedded runtime find the package."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, _site_packages(), env.get("PYTHONPATH", "")])
    return env


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_predict_demo_builds_and_serves(tmp_path, capi_so):
    env = _cpp_env()
    build = subprocess.run(["make", "predict_demo"], cwd=_DIR, env=env,
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr[-2000:]

    prefix = str(tmp_path / "model")
    mk = subprocess.run([sys.executable,
                         os.path.join(_DIR, "make_model.py"), prefix],
                        cwd=_DIR, env=env, capture_output=True, text=True,
                        timeout=300)
    assert mk.returncode == 0, mk.stderr[-2000:]

    run = subprocess.run([os.path.join(_DIR, "predict_demo"), prefix],
                         cwd=_DIR, env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    assert "PREDICT_DEMO_OK" in run.stdout
    assert "output shape: (2, 4)" in run.stdout
    # softmax rows sum to 1 each
    assert "(sum 2.0000)" in run.stdout


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_train_demo_learns(tmp_path, capi_so):
    """Full TRAINING through the C++ binding package: symbolic MLP built
    with Operator/Symbol, Executor fwd+bwd, Optimizer in-place updates —
    the cpp-package/example/mlp.cpp analog."""
    env = _cpp_env()
    build = subprocess.run(["make", "train_demo"], cwd=_DIR, env=env,
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr[-2000:]

    run = subprocess.run([os.path.join(_DIR, "train_demo")],
                         cwd=str(tmp_path), env=env, capture_output=True,
                         text=True, timeout=600)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    assert "TRAIN_DEMO_OK" in run.stdout


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_cpp_custom_op_demo(capi_so):
    """A custom operator defined ENTIRELY in C through the
    MXCustomOpRegister struct protocol (c_api.h:3029, custom.cc:70-119):
    prop creator + list/infer/create callbacks + fwd/bwd kernels, driven
    through MXImperativeInvokeByName('Custom') and MXAutogradBackward."""
    env = _cpp_env()
    build = subprocess.run(["make", "custom_op_demo"], cwd=_DIR, env=env,
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run([os.path.join(_DIR, "custom_op_demo")], cwd=_DIR,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    assert "PASS" in run.stdout


@pytest.mark.skipif(shutil.which("perl") is None
                    or shutil.which("g++") is None
                    or shutil.which("make") is None,
                    reason="needs perl + g++ + make")
def test_perl_binding(capi_so):
    """L9: the AI::MXNetTPU Perl binding (perl-package/ — the reference's
    AI::MXNet analog at minimal scale): XS CAPI shim + pure-Perl NDArray
    whose operators dispatch through MXImperativeInvokeByName."""
    pdir = os.path.join(_REPO, "perl-package", "AI-MXNetTPU")
    env = _cpp_env()
    cfg = subprocess.run(["perl", "Makefile.PL"], cwd=pdir, env=env,
                         capture_output=True, text=True, timeout=300)
    assert cfg.returncode == 0, cfg.stderr[-2000:]
    build = subprocess.run(["make"], cwd=pdir, env=env,
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run(["perl", "-Mblib", "t/basic.t"], cwd=pdir,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr[-2000:]
    assert "ok 8" in run.stdout and "not ok" not in run.stdout, run.stdout
