"""Native runtime tests (engine/storage/recordio — src/native/).

Models: tests/cpp/engine/threaded_engine_test.cc (dependency ordering,
exception propagation), tests/cpp/storage/storage_test.cc (pool reuse),
recordio roundtrips from tests/python/unittest/test_recordio.py.
"""
import os
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import storage
from incubator_mxnet_tpu._native import get_lib
from incubator_mxnet_tpu.engine import NativeEngine

pytestmark = pytest.mark.skipif(get_lib() is None,
                                reason="native library unavailable")


# ------------------------------------------------------------------ engine

def test_engine_write_serialization():
    """Writes to one var execute in push order (versioned-Var FIFO)."""
    eng = NativeEngine(num_workers=4)
    var = eng.new_var()
    seq = []
    for i in range(50):
        eng.push(lambda i=i: seq.append(i), mutable_vars=[var])
    eng.wait_for_all()
    assert seq == list(range(50))
    eng.close()


def test_engine_reads_run_concurrently():
    """Readers of one var overlap; a writer waits for all of them."""
    eng = NativeEngine(num_workers=4)
    var = eng.new_var()
    barrier = threading.Barrier(3, timeout=10)
    hits = []

    def reader():
        barrier.wait()  # deadlocks unless 3 readers run concurrently
        hits.append("r")

    for _ in range(3):
        eng.push(reader, const_vars=[var])
    eng.push(lambda: hits.append("w"), mutable_vars=[var])
    eng.wait_for_all()
    assert hits[:3] == ["r", "r", "r"] and hits[3] == "w"
    eng.close()


def test_engine_independent_vars_parallel():
    eng = NativeEngine(num_workers=4)
    v1, v2 = eng.new_var(), eng.new_var()
    order = []
    ev = threading.Event()

    def slow():
        ev.wait(5)
        order.append("slow")

    def fast():
        order.append("fast")
        ev.set()

    eng.push(slow, mutable_vars=[v1])
    eng.push(fast, mutable_vars=[v2])
    eng.wait_for_all()
    assert order == ["fast", "slow"]  # independent vars → no serialization
    eng.close()


def test_engine_dependency_chain():
    """read-after-write and write-after-read across two vars."""
    eng = NativeEngine(num_workers=4)
    a, b = eng.new_var(), eng.new_var()
    state = {}
    eng.push(lambda: state.__setitem__("x", 1), mutable_vars=[a])
    eng.push(lambda: state.__setitem__("y", state["x"] + 1),
             const_vars=[a], mutable_vars=[b])
    eng.push(lambda: state.__setitem__("z", state["y"] + 1),
             const_vars=[b])
    eng.wait_for_all()
    assert state == {"x": 1, "y": 2, "z": 3}
    eng.close()


def test_engine_rejects_overlapping_vars():
    """const/mutable overlap would self-deadlock; must raise instead."""
    eng = NativeEngine(num_workers=2)
    v = eng.new_var()
    with pytest.raises(ValueError):
        eng.push(lambda: None, const_vars=[v], mutable_vars=[v])
    with pytest.raises(ValueError):
        eng.push(lambda: None, mutable_vars=[v, v])
    eng.close()


def test_engine_exception_at_wait():
    """Errors in async ops surface at wait_for_var, like WaitToRead
    (threaded_engine.h:495 exception capture)."""
    eng = NativeEngine(num_workers=2)
    var = eng.new_var()

    def boom():
        raise ValueError("async failure")

    eng.push(boom, mutable_vars=[var], name="boom_op")
    with pytest.raises(mx.MXNetError):
        eng.wait_for_var(var)
    # a successful write clears the sticky error
    eng.push(lambda: None, mutable_vars=[var])
    eng.wait_for_var(var)
    eng.close()


def test_engine_wait_for_all_error():
    eng = NativeEngine(num_workers=2)
    var = eng.new_var()
    eng.push(lambda: 1 / 0, mutable_vars=[var])
    with pytest.raises(mx.MXNetError):
        eng.wait_for_all()
    # error reported once; engine remains usable
    eng.push(lambda: None, mutable_vars=[var])
    eng.wait_for_all()
    eng.close()


# ----------------------------------------------------------------- storage

def test_storage_pool_reuse():
    storage.empty_cache()
    h1 = storage.alloc(10000)
    p1 = h1.ptr
    h1.array[:] = 7
    storage.free(h1)
    assert storage.pooled_bytes() > 0
    h2 = storage.alloc(9000)   # same power-of-two bucket → same buffer
    assert h2.ptr == p1
    storage.free(h2)
    storage.empty_cache()
    assert storage.pooled_bytes() == 0


def test_shared_memory_roundtrip():
    name = "mxtpu_test_%d" % os.getpid()
    a = storage.SharedMemory(name, 4096, create=True)
    try:
        a.array[:16] = np.arange(16, dtype=np.uint8)
        b = storage.SharedMemory(name, 4096, create=False)
        np.testing.assert_array_equal(b.array[:16],
                                      np.arange(16, dtype=np.uint8))
        b.close()
    finally:
        a.close()


# ---------------------------------------------------------------- recordio

def test_native_recordio_roundtrip(tmp_path):
    from incubator_mxnet_tpu import recordio
    path = str(tmp_path / "native.rec")
    w = recordio.MXRecordIO(path, "w")
    assert w._nh, "native writer not engaged"
    records = [b"hello", b"x" * 1000, b"", os.urandom(257)]
    # payload containing the magic word → multi-part record
    records.append(b"abc" + (0xced7230a).to_bytes(4, "little") + b"def")
    for r in records:
        w.write(r)
    w.close()

    r = recordio.MXRecordIO(path, "r")
    assert r._nh, "native reader not engaged"
    got = []
    while True:
        item = r.read()
        if item is None:
            break
        got.append(item)
    r.close()
    assert got == records


def test_native_python_recordio_interop(tmp_path, monkeypatch):
    """Files written natively parse with the pure-python reader and
    vice versa."""
    from incubator_mxnet_tpu import recordio
    path = str(tmp_path / "interop.rec")
    records = [b"first", os.urandom(100),
               b"magic:" + (0xced7230a).to_bytes(4, "little") * 2 + b"end"]
    w = recordio.MXRecordIO(path, "w")      # native write
    for r in records:
        w.write(r)
    w.close()

    r = recordio.MXRecordIO(path, "r")      # force python read
    if r._nh:
        r._nlib.MXTRecordIOReaderFree(r._nh)
        r._nh = None
        r.fh = open(path, "rb")
    got = []
    while True:
        item = r.read()
        if item is None:
            break
        got.append(item)
    r.close()
    assert got == records


def test_native_indexed_recordio(tmp_path):
    from incubator_mxnet_tpu import recordio
    rec_path = str(tmp_path / "d.rec")
    idx_path = str(tmp_path / "d.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    for i in range(10):
        w.write_idx(i, ("record%d" % i).encode())
    w.close()
    r = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert r.read_idx(7) == b"record7"
    assert r.read_idx(2) == b"record2"
    r.close()


def test_engine_as_io_pipeline(tmp_path):
    """Realistic use: overlapped checkpoint-style writes with dependency
    ordering (write file → read it back), as the host engine is meant to
    be used around XLA compute."""
    eng = NativeEngine(num_workers=2)
    fvar = eng.new_var()
    path = str(tmp_path / "ckpt.bin")
    payload = os.urandom(1 << 16)
    result = {}

    eng.push(lambda: open(path, "wb").write(payload), mutable_vars=[fvar])
    eng.push(lambda: result.__setitem__("data", open(path, "rb").read()),
             const_vars=[fvar])
    eng.wait_for_all()
    assert result["data"] == payload
    eng.close()


def test_resource_manager_temp_space_and_rng():
    """ResourceManager parity (resource.h:38-130): pooled host scratch is
    reused across requests; parallel random streams are independent."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.resource import ResourceRequest, request

    r = request(ResourceRequest.kTempSpace)
    a = r.get_space((16, 4), "float32")
    a[:] = 7.0
    b = r.get_space((8,), "float32")  # smaller: same slot buffer reused
    assert a.__array_interface__["data"][0] == \
        b.__array_interface__["data"][0]  # same backing buffer (slot reuse)
    big = r.get_space((64, 64), "float64")
    assert big.shape == (64, 64) and big.dtype == np.float64

    pr1 = request(ResourceRequest.kParallelRandom)
    pr2 = request(ResourceRequest.kParallelRandom)
    k1, k2 = pr1.get_random(), pr2.get_random()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))

    rr = request(ResourceRequest.kRandom)
    assert np.asarray(rr.get_random()).shape == np.asarray(k1).shape


def test_engine_sanitizer_harness():
    """SURVEY §5.2: the C++ engine stress test (writes serialize per var,
    reads overlap, sticky errors, clean drain) — the same binary builds
    under -fsanitize=address/thread via `make asan-check` / `tsan-check`."""
    import shutil
    import subprocess

    if shutil.which("make") is None:
        import pytest

        pytest.skip("no make")
    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "native")
    # --always-make: a checked-out stale binary must never be what runs
    run = subprocess.run(["make", "--always-make", "engine-check"],
                         cwd=native, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr[-1500:]
    assert "ENGINE_TEST_OK" in run.stdout


# ------------------------------------------------- build on demand (PR 23)

def _no_make(*_args, **_kwargs):
    raise FileNotFoundError(2, "No such file or directory: 'make'")


def test_build_returns_an_up_to_date_library_without_make(monkeypatch):
    """A checkout that was built once loads its library on a host with no
    toolchain: ``build`` looks before it makes."""
    from incubator_mxnet_tpu import _native

    path = _native.build()  # exists from here on
    monkeypatch.setattr(_native.subprocess, "run", _no_make)
    assert _native.build() == path


def test_build_returns_an_up_to_date_library_from_a_read_only_checkout(
        monkeypatch):
    """No lock file can be made in a read-only ``src/native``; nothing can
    be half-way through a link there either, so a fresh library loads."""
    from incubator_mxnet_tpu import _native

    path = _native.build()

    def read_only(name, *args, **kwargs):
        raise PermissionError(13, "Read-only file system", name)

    monkeypatch.setattr(_native, "open", read_only, raising=False)
    monkeypatch.setattr(_native.subprocess, "run", _no_make)
    assert _native.build() == path
    monkeypatch.setattr(_native, "_fresh", lambda path: False)
    with pytest.raises(PermissionError):
        _native.build()


def test_a_stale_library_is_rebuilt_or_refused_never_loaded(monkeypatch,
                                                            tmp_path):
    """A library older than a source beside it is not up to date: with a
    toolchain it is rebuilt, without one ``build`` raises and ``get_lib``
    answers None (the pure-Python host runtime) instead of loading it."""
    import shutil

    from incubator_mxnet_tpu import _native

    src = tmp_path / "native"
    src.mkdir()
    for name in os.listdir(_native._SRC_DIR):
        if name == "Makefile" or name.endswith(".cc"):
            shutil.copy2(os.path.join(_native._SRC_DIR, name), src / name)
    monkeypatch.setattr(_native, "_SRC_DIR", str(src))
    lib = _native.build()
    assert os.path.dirname(lib) == str(src) and _native._fresh(lib)
    built = os.path.getmtime(lib)
    os.utime(src / "engine.cc", (built + 10, built + 10))
    assert not _native._fresh(lib)
    assert os.path.getmtime(_native.build()) > built  # make ran

    os.utime(src / "engine.cc", None)  # newer than the rebuilt library
    os.utime(lib, (built, built))
    monkeypatch.setattr(_native.subprocess, "run", _no_make)
    with pytest.raises(OSError):
        _native.build()
    monkeypatch.setattr(_native, "_LIB", None)
    monkeypatch.setattr(_native, "_TRIED", False)
    assert _native.get_lib() is None
