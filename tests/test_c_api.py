"""C ABI surface (src/native/c_api.cc — the include/mxnet/c_api.h +
c_predict_api.h contract driven through ctypes exactly as a C consumer
would)."""
import ctypes
import json
import os
import shutil

import numpy as np
import pytest

from incubator_mxnet_tpu import _native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lib():
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no make/g++ to build libmxtpu_capi.so from source")
    lib = ctypes.CDLL(_native.build("libmxtpu_capi.so"))
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


def _check(lib, rc):
    assert rc == 0, lib.MXGetLastError().decode()


def test_version(lib):
    v = ctypes.c_int()
    _check(lib, lib.MXGetVersion(ctypes.byref(v)))
    assert v.value == 10600


def test_ndarray_create_copy_shape(lib):
    shape = (ctypes.c_uint32 * 2)(3, 4)
    h = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0,
                                      ctypes.byref(h)))
    data = np.arange(12, dtype=np.float32)
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, data.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(12)))
    out = np.zeros(12, np.float32)
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(12)))
    np.testing.assert_array_equal(out, data)

    ndim = ctypes.c_uint32()
    pdata = ctypes.POINTER(ctypes.c_uint32)()
    _check(lib, lib.MXNDArrayGetShape(h, ctypes.byref(ndim),
                                      ctypes.byref(pdata)))
    assert [pdata[i] for i in range(ndim.value)] == [3, 4]
    dt = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetDType(h, ctypes.byref(dt)))
    assert dt.value == 0  # kFloat32
    _check(lib, lib.MXNDArrayFree(h))


def test_imperative_invoke_by_name(lib):
    shape = (ctypes.c_uint32 * 2)(2, 3)
    a = ctypes.c_void_p()
    b = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0, ctypes.byref(a)))
    _check(lib, lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0, ctypes.byref(b)))
    av = np.full(6, 2.0, np.float32)
    bv = np.full(6, 5.0, np.float32)
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        a, av.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(6)))
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        b, bv.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(6)))

    inputs = (ctypes.c_void_p * 2)(a, b)
    n_out = ctypes.c_int()
    outputs = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXImperativeInvokeByName(
        b"broadcast_add", 2, inputs, ctypes.byref(n_out),
        ctypes.byref(outputs), 0, None, None))
    assert n_out.value == 1
    out = np.zeros(6, np.float32)
    o = ctypes.c_void_p(outputs[0])
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        o, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(6)))
    np.testing.assert_array_equal(out, np.full(6, 7.0, np.float32))
    lib.MXNDArrayFree(a)
    lib.MXNDArrayFree(b)
    lib.MXNDArrayFree(o)


def test_op_list(lib):
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr)))
    names = {arr[i].decode() for i in range(n.value)}
    assert n.value > 200
    assert {"Convolution", "BatchNorm", "FullyConnected"} <= names


def test_ndarray_save_load_roundtrip(lib, tmp_path):
    shape = (ctypes.c_uint32 * 1)(4)
    h = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0, ctypes.byref(h)))
    vals = np.array([1, 2, 3, 4], np.float32)
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, vals.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    path = str(tmp_path / "a.params").encode()
    keys = (ctypes.c_char_p * 1)(b"w")
    handles = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.MXNDArraySave(path, 1, handles, keys))

    out_size = ctypes.c_uint32()
    out_arr = ctypes.POINTER(ctypes.c_void_p)()
    name_size = ctypes.c_uint32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXNDArrayLoad(path, ctypes.byref(out_size),
                                  ctypes.byref(out_arr),
                                  ctypes.byref(name_size),
                                  ctypes.byref(names)))
    assert out_size.value == 1 and name_size.value == 1
    assert names[0].decode() == "w"
    got = np.zeros(4, np.float32)
    o = ctypes.c_void_p(out_arr[0])
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        o, got.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    np.testing.assert_array_equal(got, vals)
    lib.MXNDArrayFree(h)
    lib.MXNDArrayFree(o)


def test_symbol_json_roundtrip(lib):
    import incubator_mxnet_tpu.symbol as sym

    s = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                           num_hidden=4)
    js = s.tojson().encode()
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(js, ctypes.byref(h)))
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXSymbolListArguments(h, ctypes.byref(n),
                                          ctypes.byref(arr)))
    assert [arr[i].decode() for i in range(n.value)] == ["data", "w", "b"]
    out_json = ctypes.c_char_p()
    _check(lib, lib.MXSymbolSaveToJSON(h, ctypes.byref(out_json)))
    parsed = json.loads(out_json.value.decode())
    assert any(node.get("op") == "FullyConnected"
               for node in parsed["nodes"])
    lib.MXSymbolFree(h)


def test_predict_api_end_to_end(lib, tmp_path):
    """The serving path: build+save a model in Python, serve it through the
    C predict ABI only (MXPredCreate → SetInput → Forward → GetOutput)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    import incubator_mxnet_tpu.symbol as sym
    from incubator_mxnet_tpu.ndarray import legacy_io

    rng = np.random.RandomState(0)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    b = np.zeros(4, np.float32)
    out = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                             num_hidden=4)
    out = sym.Activation(out, act_type="tanh")
    blob = legacy_io.save_legacy([nd.array(w), nd.array(b)],
                                 ["arg:w", "arg:b"])
    json_str = out.tojson().encode()

    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shape_data = (ctypes.c_uint32 * 2)(2, 6)
    keys = (ctypes.c_char_p * 1)(b"data")
    h = ctypes.c_void_p()
    _check(lib, lib.MXPredCreate(json_str, blob, len(blob), 1, 0, 1, keys,
                                 indptr, shape_data, ctypes.byref(h)))
    x = rng.normal(size=(2, 6)).astype(np.float32)
    _check(lib, lib.MXPredSetInput(
        h, b"data", x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint32(12)))
    _check(lib, lib.MXPredForward(h))
    sdata = ctypes.POINTER(ctypes.c_uint32)()
    sdim = ctypes.c_uint32()
    _check(lib, lib.MXPredGetOutputShape(h, 0, ctypes.byref(sdata),
                                         ctypes.byref(sdim)))
    oshape = [sdata[i] for i in range(sdim.value)]
    assert oshape == [2, 4]
    got = np.zeros(8, np.float32)
    _check(lib, lib.MXPredGetOutput(
        h, 0, got.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint32(8)))
    expect = np.tanh(x @ w.T + b)
    np.testing.assert_allclose(got.reshape(2, 4), expect, rtol=1e-5,
                               atol=1e-6)
    lib.MXPredFree(h)


def test_atomic_symbol_info_reflection(lib):
    """Op reflection through the ABI (MXSymbolListAtomicSymbolCreators +
    MXSymbolGetAtomicSymbolInfo, src/c_api/c_api_symbolic.cc) — the surface
    bindings code-gen op wrappers from."""
    n = ctypes.c_uint32()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(creators)))
    assert n.value > 250
    names = [ctypes.cast(creators[i], ctypes.c_char_p).value.decode()
             for i in range(n.value)]
    assert "Convolution" in names
    idx = names.index("sgd_mom_update")

    name = ctypes.c_char_p()
    desc = ctypes.c_char_p()
    nargs = ctypes.c_uint32()
    arg_names = ctypes.POINTER(ctypes.c_char_p)()
    arg_types = ctypes.POINTER(ctypes.c_char_p)()
    arg_descs = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXSymbolGetAtomicSymbolInfo(
        ctypes.c_void_p(creators[idx]), ctypes.byref(name),
        ctypes.byref(desc),
        ctypes.byref(nargs), ctypes.byref(arg_names),
        ctypes.byref(arg_types), ctypes.byref(arg_descs)))
    assert name.value.decode() == "sgd_mom_update"
    got = {arg_names[i].decode(): arg_types[i].decode()
           for i in range(nargs.value)}
    assert got["weight"] == "NDArray"
    assert got["mom"] == "NDArray"
    assert got["lr"].startswith("float, optional")


def test_symbol_compose_and_executor_roundtrip(lib):
    """MXSymbolCreateVariable/CreateFromOp + MXExecutorBind/Forward/Backward
    driven as a raw C consumer: d/dx sum(2x) == 2."""
    x = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"x", ctypes.byref(x)))
    keys = (ctypes.c_char_p * 1)(b"scalar")
    vals = (ctypes.c_char_p * 1)(b"2.0")
    ins = (ctypes.c_void_p * 1)(x)
    y = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromOp(
        b"_mul_scalar", 1, keys, vals, 1, None, ins, b"y", ctypes.byref(y)))

    shape = (ctypes.c_uint32 * 1)(4)
    arr = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                      ctypes.byref(arr)))
    data = np.arange(4, dtype=np.float32)
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        arr, data.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    grad = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                      ctypes.byref(grad)))

    args = (ctypes.c_void_p * 1)(arr)
    grads = (ctypes.c_void_p * 1)(grad)
    reqs = (ctypes.c_uint32 * 1)(1)  # kWriteTo
    exe = ctypes.c_void_p()
    _check(lib, lib.MXExecutorBind(y, 1, 0, 1, args, grads, reqs, 0, None,
                                   ctypes.byref(exe)))
    _check(lib, lib.MXExecutorForward(exe, 1))
    n_out = ctypes.c_uint32()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXExecutorOutputs(exe, ctypes.byref(n_out),
                                      ctypes.byref(outs)))
    assert n_out.value == 1
    out = np.zeros(4, np.float32)
    o = ctypes.c_void_p(outs[0])
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        o, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    np.testing.assert_allclose(out, 2.0 * data)

    _check(lib, lib.MXExecutorBackward(exe, 0, None))
    g = np.zeros(4, np.float32)
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        grad, g.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(4)))
    np.testing.assert_allclose(g, np.full(4, 2.0, np.float32))

    lib.MXExecutorFree(exe)
    lib.MXSymbolFree(x)
    lib.MXSymbolFree(y)
    lib.MXNDArrayFree(arr)
    lib.MXNDArrayFree(grad)
    lib.MXNDArrayFree(o)


def _make_nd(lib, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    h = ctypes.c_void_p()
    shape = (ctypes.c_uint32 * arr.ndim)(*arr.shape)
    _check(lib, lib.MXNDArrayCreateEx(shape, arr.ndim, 1, 0, 0, 0,
                                      ctypes.byref(h)))
    _check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, arr.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(arr.size)))
    return h


def _to_np(lib, h, shape):
    out = np.zeros(shape, np.float32)
    _check(lib, lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(out.size)))
    return out


def _py_handle(obj):
    """NDArrayHandle of an in-process Python NDArray: handles ARE the
    PyObject* (c_api.cc header contract), and CPython's id() is the
    object address."""
    return ctypes.c_void_p(id(obj))


def test_autograd_abi(lib):
    """MXAutogradMarkVariables / SetIsRecording / Backward / GetGrad
    (c_api.h autograd block): d(x*x)/dx == 2x through the C ABI."""
    x = _make_nd(lib, np.array([1., 2., 3.], np.float32))
    g = _make_nd(lib, np.zeros(3, np.float32))
    _check(lib, lib.MXAutogradMarkVariables(
        1, (ctypes.c_void_p * 1)(x), (ctypes.c_uint32 * 1)(1),
        (ctypes.c_void_p * 1)(g)))
    prev = ctypes.c_int()
    _check(lib, lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)))
    outp = ctypes.POINTER(ctypes.c_void_p)()
    n = ctypes.c_int(0)
    _check(lib, lib.MXImperativeInvokeByName(
        b"elemwise_mul", 2, (ctypes.c_void_p * 2)(x, x), ctypes.byref(n),
        ctypes.byref(outp), 0, None, None))
    y = ctypes.c_void_p(outp[0])
    _check(lib, lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)))
    _check(lib, lib.MXAutogradBackward(1, (ctypes.c_void_p * 1)(y), None, 0))
    gh = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayGetGrad(x, ctypes.byref(gh)))
    np.testing.assert_allclose(_to_np(lib, gh, (3,)), [2., 4., 6.])
    rec = ctypes.c_bool()
    _check(lib, lib.MXAutogradIsRecording(ctypes.byref(rec)))
    assert not rec.value


def test_kvstore_abi_with_c_updater(lib):
    """MXKVStoreCreate/Init/Push/Pull/SetUpdater: the C updater callback
    fires at push (kvstore.h:269 set_updater contract). recv/local
    arrive as OWNED handles the callee must MXNDArrayFree (the
    reference frontend wraps both in owning NDArrays)."""
    UPDATER = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                               ctypes.c_void_p, ctypes.c_void_p)
    calls = []

    @UPDATER
    def upd(key, recv, local, handle):
        calls.append(key)
        lib.MXNDArrayFree(ctypes.c_void_p(recv))
        lib.MXNDArrayFree(ctypes.c_void_p(local))

    kv = ctypes.c_void_p()
    _check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    _check(lib, lib.MXKVStoreSetUpdater(kv, upd, None))
    keys = (ctypes.c_int * 1)(3)
    _check(lib, lib.MXKVStoreInit(
        kv, 1, keys, (ctypes.c_void_p * 1)(
            _make_nd(lib, np.ones(4, np.float32)))))
    _check(lib, lib.MXKVStorePush(
        kv, 1, keys, (ctypes.c_void_p * 1)(
            _make_nd(lib, np.full(4, 0.5, np.float32))), 0))
    dst = _make_nd(lib, np.zeros(4, np.float32))
    _check(lib, lib.MXKVStorePull(kv, 1, keys, (ctypes.c_void_p * 1)(dst),
                                  0))
    assert calls == [3]
    rank = ctypes.c_int()
    size = ctypes.c_int()
    _check(lib, lib.MXKVStoreGetRank(kv, ctypes.byref(rank)))
    _check(lib, lib.MXKVStoreGetGroupSize(kv, ctypes.byref(size)))
    assert (rank.value, size.value) == (0, 1)
    _check(lib, lib.MXKVStoreFree(kv))


def test_recordio_abi(lib, tmp_path):
    p = str(tmp_path / "t.rec").encode()
    w = ctypes.c_void_p()
    _check(lib, lib.MXRecordIOWriterCreate(p, ctypes.byref(w)))
    _check(lib, lib.MXRecordIOWriterWriteRecord(w, b"hello-capi", 10))
    pos = ctypes.c_size_t()
    _check(lib, lib.MXRecordIOWriterTell(w, ctypes.byref(pos)))
    _check(lib, lib.MXRecordIOWriterFree(w))
    r = ctypes.c_void_p()
    _check(lib, lib.MXRecordIOReaderCreate(p, ctypes.byref(r)))
    buf = ctypes.c_char_p()
    sz = ctypes.c_size_t()
    _check(lib, lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                               ctypes.byref(sz)))
    assert ctypes.string_at(buf, sz.value) == b"hello-capi"
    # EOF -> NULL/0
    _check(lib, lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                               ctypes.byref(sz)))
    assert sz.value == 0
    _check(lib, lib.MXRecordIOReaderFree(r))


def test_dataiter_abi(lib):
    ns = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXListDataIters(ctypes.byref(ns), ctypes.byref(arr)))
    names = [arr[i].decode() for i in range(ns.value)]
    assert "MNISTIter" in names and "ImageRecordIter" in names


def test_cached_op_abi(lib):
    """MXCreateCachedOp + MXInvokeCachedOp: compiled-once replay of a
    symbol (src/imperative/cached_op.cc contract)."""
    v = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(v)))
    s = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromOp(
        b"relu", 0, (ctypes.c_char_p * 0)(), (ctypes.c_char_p * 0)(),
        1, (ctypes.c_char_p * 1)(b"data"), (ctypes.c_void_p * 1)(v),
        b"act0", ctypes.byref(s)))
    cop = ctypes.c_void_p()
    _check(lib, lib.MXCreateCachedOp(s, ctypes.byref(cop)))
    xin = _make_nd(lib, np.array([-1., 2., -3., 4.], np.float32))
    no = ctypes.c_int(0)
    couts = ctypes.POINTER(ctypes.c_void_p)()
    for _ in range(2):  # second call replays the cached executable
        _check(lib, lib.MXInvokeCachedOp(cop, 1, (ctypes.c_void_p * 1)(xin),
                                         ctypes.byref(no),
                                         ctypes.byref(couts)))
    np.testing.assert_allclose(
        _to_np(lib, ctypes.c_void_p(couts[0]), (4,)), [0., 2., 0., 4.])
    _check(lib, lib.MXFreeCachedOp(cop))


def test_misc_runtime_abi(lib):
    _check(lib, lib.MXRandomSeed(7))
    _check(lib, lib.MXEngineWaitAll())
    _check(lib, lib.MXNotifyShutdown())
    _check(lib, lib.MXSetNumOMPThreads(4))
    _check(lib, lib.MXStorageEmptyCache(1, 0))


def test_profiler_abi(lib, tmp_path):
    """MXSetProfilerConfig/State + MXProfile* object surface
    (c_api.h profiler block; reference src/c_api/c_api_profile.cc)."""
    fname = str(tmp_path / "prof.json")
    keys = (ctypes.c_char_p * 1)(b"filename")
    vals = (ctypes.c_char_p * 1)(fname.encode())
    _check(lib, lib.MXSetProfilerConfig(1, keys, vals))
    _check(lib, lib.MXSetProfilerState(1))
    dom = ctypes.c_void_p()
    _check(lib, lib.MXProfileCreateDomain(b"capi", ctypes.byref(dom)))
    task = ctypes.c_void_p()
    _check(lib, lib.MXProfileCreateTask(dom, b"task0", ctypes.byref(task)))
    _check(lib, lib.MXProfileDurationStart(task))
    _check(lib, lib.MXProfileDurationStop(task))
    ctr = ctypes.c_void_p()
    _check(lib, lib.MXProfileCreateCounter(dom, b"ctr0", ctypes.byref(ctr)))
    _check(lib, lib.MXProfileSetCounter(ctr, ctypes.c_uint64(5)))
    _check(lib, lib.MXProfileAdjustCounter(ctr, ctypes.c_int64(-2)))
    _check(lib, lib.MXProfileSetMarker(dom, b"mark0", b"process"))
    out = ctypes.c_char_p()
    _check(lib, lib.MXAggregateProfileStatsPrint(ctypes.byref(out), 0))
    stats = out.value.decode()
    assert stats.startswith("Name") and "task0" in stats, stats
    _check(lib, lib.MXSetProfilerState(0))
    for h in (task, ctr, dom):
        _check(lib, lib.MXProfileDestroyHandle(h))


def test_serving_bundle(tmp_path):
    """tools/make_serving_bundle.py (amalgamation/ analog): the bundle
    serves through MXPred* from a clean environment with nothing from the
    repo on the path."""
    import subprocess
    import sys

    bundle = str(tmp_path / "bundle")
    prefix = str(tmp_path / "model")
    rc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "cpp-package", "make_model.py"),
         prefix], capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    rc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "make_serving_bundle.py"),
         prefix, bundle, "[2, 8]"],
        capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    run = subprocess.run(
        [sys.executable, os.path.join(bundle, "serve.py")],
        capture_output=True, text=True, cwd=bundle,
        env={"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu"},
        timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "SERVE OK" in run.stdout


def test_func_registry_abi(lib):
    """MXListFunctions / MXFuncGetInfo / MXFuncInvoke (legacy function
    registry over the op registry)."""
    ns = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXListFunctions(ctypes.byref(ns), ctypes.byref(arr)))
    assert ns.value > 300
    # handles are interned op names; walk for 'relu' via MXFuncGetInfo
    name = ctypes.c_char_p()
    desc = ctypes.c_char_p()
    na = ctypes.c_uint32()
    anames = ctypes.POINTER(ctypes.c_char_p)()
    atypes = ctypes.POINTER(ctypes.c_char_p)()
    adescs = ctypes.POINTER(ctypes.c_char_p)()
    rett = ctypes.c_char_p()
    found = None
    for i in range(ns.value):
        _check(lib, lib.MXFuncGetInfo(
            ctypes.c_void_p(arr[i]), ctypes.byref(name), ctypes.byref(desc),
            ctypes.byref(na), ctypes.byref(anames), ctypes.byref(atypes),
            ctypes.byref(adescs), ctypes.byref(rett)))
        if name.value == b"relu":
            found = ctypes.c_void_p(arr[i])
            break
    assert found is not None
    x = _make_nd(lib, np.array([-1.0, 2.0, -3.0], np.float32))
    out = _make_nd(lib, np.zeros(3, np.float32))
    _check(lib, lib.MXFuncInvoke(found, (ctypes.c_void_p * 1)(x), None,
                                 (ctypes.c_void_p * 1)(out), 1, 0, 1))
    np.testing.assert_allclose(_to_np(lib, out, (3,)), [0.0, 2.0, 0.0])


def test_rtc_abi(lib):
    """MXRtcCudaModule*/Kernel* over runtime Pallas compilation (rtc.py)."""
    src = b"""
def scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 3.0
"""
    mod = ctypes.c_void_p()
    exports = (ctypes.c_char_p * 1)(b"scale_kernel")
    _check(lib, lib.MXRtcCudaModuleCreate(src, 0, None, 1, exports,
                                          ctypes.byref(mod)))
    kern = ctypes.c_void_p()
    _check(lib, lib.MXRtcCudaKernelCreate(mod, b"scale_kernel", 0, None,
                                          None, None, ctypes.byref(kern)))
    x = _make_nd(lib, np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
    out = _make_nd(lib, np.zeros((2, 2), np.float32))
    args = (ctypes.c_void_p * 2)(x, out)
    _check(lib, lib.MXRtcCudaKernelCall(kern, 0, args, 1, 1))
    np.testing.assert_allclose(_to_np(lib, out, (2, 2)),
                               [[3.0, 6.0], [9.0, 12.0]])
    _check(lib, lib.MXRtcCudaKernelFree(kern))
    _check(lib, lib.MXRtcCudaModuleFree(mod))


def test_engine_push_abi(lib):
    """MXEnginePushSyncND / MXEnginePushAsyncND + MXNDArrayWaitToWrite:
    C callbacks scheduled through the host dependency engine."""
    ENGINE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
    hits = []

    @ENGINE_FN
    def work(data):
        hits.append(int(data or 0))

    nd1 = _make_nd(lib, np.ones(4, np.float32))
    _check(lib, lib.MXEnginePushSyncND(
        work, ctypes.c_void_p(7), None, None,
        (ctypes.c_void_p * 1)(nd1), 1, None, 0))
    assert hits == [7]
    _check(lib, lib.MXEnginePushAsyncND(
        work, ctypes.c_void_p(9), None, None,
        None, 0, (ctypes.c_void_p * 1)(nd1), 1))
    _check(lib, lib.MXNDArrayWaitToWrite(nd1))
    _check(lib, lib.MXEngineWaitAll())
    assert hits == [7, 9]


def test_gpu_queries_abi(lib):
    n = ctypes.c_int(-1)
    _check(lib, lib.MXGetGPUCount(ctypes.byref(n)))
    assert n.value == 0
    free = ctypes.c_uint64()
    tot = ctypes.c_uint64()
    _check(lib, lib.MXGetGPUMemoryInformation64(0, ctypes.byref(free),
                                                ctypes.byref(tot)))


def test_symbol_tail_abi(lib, tmp_path):
    """MXSymbolGetName/Attr/SetAttr/Copy/Internals/GetOutput/InferType/
    SaveToFile/CreateFromFile/Print."""
    v = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(v)))
    s = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromOp(
        b"relu", 0, (ctypes.c_char_p * 0)(), (ctypes.c_char_p * 0)(),
        1, (ctypes.c_char_p * 1)(b"data"), (ctypes.c_void_p * 1)(v),
        b"act0", ctypes.byref(s)))
    name = ctypes.c_char_p()
    ok = ctypes.c_int()
    _check(lib, lib.MXSymbolGetName(s, ctypes.byref(name), ctypes.byref(ok)))
    assert name.value == b"act0" and ok.value == 1
    _check(lib, lib.MXSymbolSetAttr(s, b"__lr_mult__", b"2.0"))
    val = ctypes.c_char_p()
    _check(lib, lib.MXSymbolGetAttr(s, b"__lr_mult__", ctypes.byref(val),
                                    ctypes.byref(ok)))
    assert val.value == b"2.0" and ok.value == 1
    cp = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCopy(s, ctypes.byref(cp)))
    n_out = ctypes.c_uint32()
    _check(lib, lib.MXSymbolGetNumOutputs(cp, ctypes.byref(n_out)))
    assert n_out.value == 1
    internals = ctypes.c_void_p()
    _check(lib, lib.MXSymbolGetInternals(s, ctypes.byref(internals)))
    o0 = ctypes.c_void_p()
    _check(lib, lib.MXSymbolGetOutput(s, 0, ctypes.byref(o0)))
    txt = ctypes.c_char_p()
    _check(lib, lib.MXSymbolPrint(s, ctypes.byref(txt)))
    assert b"data" in txt.value
    # infer type: data f32 -> out f32
    keys = (ctypes.c_char_p * 1)(b"data")
    codes = (ctypes.c_int * 1)(0)
    isz = ctypes.c_uint32()
    osz = ctypes.c_uint32()
    asz = ctypes.c_uint32()
    ip = ctypes.POINTER(ctypes.c_int)()
    op = ctypes.POINTER(ctypes.c_int)()
    ap = ctypes.POINTER(ctypes.c_int)()
    comp = ctypes.c_int()
    _check(lib, lib.MXSymbolInferType(
        s, 1, keys, codes, ctypes.byref(isz), ctypes.byref(ip),
        ctypes.byref(osz), ctypes.byref(op), ctypes.byref(asz),
        ctypes.byref(ap), ctypes.byref(comp)))
    assert comp.value == 1 and osz.value == 1 and op[0] == 0
    # file round trip
    path = str(tmp_path / "sym.json").encode()
    _check(lib, lib.MXSymbolSaveToFile(s, path))
    s2 = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromFile(path, ctypes.byref(s2)))
    _check(lib, lib.MXSymbolGetName(s2, ctypes.byref(name),
                                    ctypes.byref(ok)))
    assert name.value == b"act0"


def test_quantize_and_subgraph_abi(lib):
    """MXQuantizeSymbol + MXGenBackendSubgraph through the C ABI."""
    v = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(v)))
    w = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"w", ctypes.byref(w)))
    s = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromOp(
        b"FullyConnected", 1, (ctypes.c_char_p * 1)(b"num_hidden"),
        (ctypes.c_char_p * 1)(b"8"), 2,
        (ctypes.c_char_p * 2)(b"data", b"weight"),
        (ctypes.c_void_p * 2)(v, w), b"fc0", ctypes.byref(s)))
    q = ctypes.c_void_p()
    _check(lib, lib.MXQuantizeSymbol(s, ctypes.byref(q), 0, None, 0, None,
                                     b"int8"))
    js = ctypes.c_char_p()
    _check(lib, lib.MXSymbolSaveToJSON(q, ctypes.byref(js)))
    assert b"_contrib_quantized_fully_connected" in js.value
    sub = ctypes.c_void_p()
    _check(lib, lib.MXGenBackendSubgraph(s, b"xla", ctypes.byref(sub)))


def test_ndarray_raw_bytes_abi(lib):
    x = _make_nd(lib, np.arange(6, dtype=np.float32).reshape(2, 3))
    buf = ctypes.c_char_p()
    sz = ctypes.c_size_t()
    _check(lib, lib.MXNDArraySaveRawBytes(x, ctypes.byref(sz),
                                          ctypes.byref(buf)))
    raw = ctypes.string_at(buf, sz.value)
    y = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayLoadFromRawBytes(raw, len(raw),
                                              ctypes.byref(y)))
    np.testing.assert_array_equal(_to_np(lib, y, (2, 3)),
                                  np.arange(6, dtype=np.float32)
                                  .reshape(2, 3))


def test_kvstore_pushpull_and_compression_abi(lib):
    kv = ctypes.c_void_p()
    _check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    keys = (ctypes.c_int * 1)(5)
    _check(lib, lib.MXKVStoreInit(
        kv, 1, keys,
        (ctypes.c_void_p * 1)(_make_nd(lib, np.zeros(4, np.float32)))))
    _check(lib, lib.MXKVStoreSetGradientCompression(
        kv, 2, (ctypes.c_char_p * 2)(b"type", b"threshold"),
        (ctypes.c_char_p * 2)(b"2bit", b"0.5")))
    g = _make_nd(lib, np.full(4, 1.0, np.float32))
    out = _make_nd(lib, np.zeros(4, np.float32))
    _check(lib, lib.MXKVStorePushPull(kv, 1, keys,
                                      (ctypes.c_void_p * 1)(g),
                                      (ctypes.c_void_p * 1)(out), 0))
    got = _to_np(lib, out, (4,))
    assert np.isfinite(got).all()
    _check(lib, lib.MXKVStoreFree(kv))


def test_ndarray_tail_abi(lib):
    """Round-4 NDArray tail: WaitAll, ShapeEx/64, Create64, Reshape64,
    Slice64/At64, storage type, GetData, grad state, shallow copy,
    SyncCopyFromNDArray, LoadFromBuffer."""
    _check(lib, lib.MXNDArrayWaitAll())

    x = _make_nd(lib, np.arange(12, dtype=np.float32).reshape(3, 4))
    ndim = ctypes.c_int()
    p_int = ctypes.POINTER(ctypes.c_int)()
    _check(lib, lib.MXNDArrayGetShapeEx(x, ctypes.byref(ndim),
                                        ctypes.byref(p_int)))
    assert [p_int[i] for i in range(ndim.value)] == [3, 4]
    p64 = ctypes.POINTER(ctypes.c_int64)()
    _check(lib, lib.MXNDArrayGetShape64(x, ctypes.byref(ndim),
                                        ctypes.byref(p64)))
    assert [p64[i] for i in range(ndim.value)] == [3, 4]

    h = ctypes.c_void_p()
    shape64 = (ctypes.c_int64 * 2)(2, 5)
    _check(lib, lib.MXNDArrayCreateEx64(shape64, 2, 1, 0, 0, 0,
                                        ctypes.byref(h)))
    _check(lib, lib.MXNDArrayGetShape64(h, ctypes.byref(ndim),
                                        ctypes.byref(p64)))
    assert [p64[i] for i in range(ndim.value)] == [2, 5]

    none = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateNone(ctypes.byref(none)))

    r = ctypes.c_void_p()
    dims = (ctypes.c_int64 * 2)(4, 3)
    _check(lib, lib.MXNDArrayReshape64(x, 2, dims, False, ctypes.byref(r)))
    np.testing.assert_array_equal(
        _to_np(lib, r, (4, 3)),
        np.arange(12, dtype=np.float32).reshape(4, 3))

    s = ctypes.c_void_p()
    _check(lib, lib.MXNDArraySlice64(x, 1, 3, ctypes.byref(s)))
    np.testing.assert_array_equal(
        _to_np(lib, s, (2, 4)),
        np.arange(12, dtype=np.float32).reshape(3, 4)[1:3])
    a = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayAt64(x, 2, ctypes.byref(a)))
    np.testing.assert_array_equal(
        _to_np(lib, a, (4,)), np.arange(12, dtype=np.float32)
        .reshape(3, 4)[2])

    st = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetStorageType(x, ctypes.byref(st)))
    assert st.value == 0  # kDefaultStorage

    ptr = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayGetData(x, ctypes.byref(ptr)))
    host = np.ctypeslib.as_array(
        ctypes.cast(ptr, ctypes.POINTER(ctypes.c_float)), (12,))
    np.testing.assert_array_equal(host, np.arange(12, dtype=np.float32))
    # writes through the GetData pointer sync back at the next wait
    # (reference returns the live chunk; here copy-on-read + write-back)
    host[0] = 99.0
    _check(lib, lib.MXNDArrayWaitToRead(x))
    assert _to_np(lib, x, (3, 4))[0, 0] == 99.0
    host[0] = 0.0
    _check(lib, lib.MXNDArrayWaitToWrite(x))
    assert _to_np(lib, x, (3, 4))[0, 0] == 0.0
    # a second GetData is itself a sync boundary: pointer writes pending
    # at the time of the call survive into the fresh buffer
    host[1] = 7.0
    _check(lib, lib.MXNDArrayGetData(x, ctypes.byref(ptr)))
    host = np.ctypeslib.as_array(
        ctypes.cast(ptr, ctypes.POINTER(ctypes.c_float)), (12,))
    assert host[1] == 7.0 and _to_np(lib, x, (3, 4))[0, 1] == 7.0
    host[1] = 1.0  # restore for the assertions below
    _check(lib, lib.MXNDArrayWaitToRead(x))

    gs = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetGradState(x, ctypes.byref(gs)))
    assert gs.value == 0
    _check(lib, lib.MXNDArraySetGradState(x, 1))
    _check(lib, lib.MXNDArrayGetGradState(x, ctypes.byref(gs)))
    assert gs.value == 1

    sc = ctypes.c_void_p()
    _check(lib, lib.MXShallowCopyNDArray(x, ctypes.byref(sc)))
    np.testing.assert_array_equal(
        _to_np(lib, sc, (3, 4)), np.arange(12, dtype=np.float32).reshape(3, 4))
    _check(lib, lib.MXNDArrayFree(sc))

    dst = _make_nd(lib, np.zeros((3, 4), np.float32))
    _check(lib, lib.MXNDArraySyncCopyFromNDArray(dst, x, -1))
    np.testing.assert_array_equal(
        _to_np(lib, dst, (3, 4)), np.arange(12, dtype=np.float32).reshape(3, 4))

    # save to buffer via the save-file ABI, reload via LoadFromBuffer
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".params", delete=False) as f:
        path = f.name
    _check(lib, lib.MXNDArraySave(path.encode(), 1,
                                  (ctypes.c_void_p * 1)(x),
                                  (ctypes.c_char_p * 1)(b"w")))
    blob = open(path, "rb").read()
    os.unlink(path)
    n_arr = ctypes.c_uint32()
    arrs = ctypes.POINTER(ctypes.c_void_p)()
    n_names = ctypes.c_uint32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXNDArrayLoadFromBuffer(
        blob, len(blob), ctypes.byref(n_arr), ctypes.byref(arrs),
        ctypes.byref(n_names), ctypes.byref(names)))
    assert n_arr.value == 1 and names[0] == b"w"
    np.testing.assert_array_equal(
        _to_np(lib, ctypes.c_void_p(arrs[0]), (3, 4)),
        np.arange(12, dtype=np.float32).reshape(3, 4))


def test_sparse_ndarray_abi(lib):
    """MXNDArrayCreateSparseEx + aux accessors + SyncCheckFormat."""
    h = ctypes.c_void_p()
    shape = (ctypes.c_uint32 * 2)(4, 6)
    aux_types = (ctypes.c_int * 2)(6, 6)  # int64 indptr / indices
    aux_ndims = (ctypes.c_uint32 * 2)(1, 1)
    aux_shape = (ctypes.c_uint32 * 2)(5, 3)  # indptr len 5, nnz 3
    _check(lib, lib.MXNDArrayCreateSparseEx(
        2, shape, 2, 1, 0, 0, 0, 2, aux_types, aux_ndims, aux_shape,
        ctypes.byref(h)))
    st = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetStorageType(h, ctypes.byref(st)))
    assert st.value == 2  # kCSRStorage
    t = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetAuxType(h, 0, ctypes.byref(t)))
    assert t.value == 6  # int64
    aux = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayGetAuxNDArray(h, 0, ctypes.byref(aux)))
    ndim = ctypes.c_int()
    p64 = ctypes.POINTER(ctypes.c_int64)()
    _check(lib, lib.MXNDArrayGetShape64(aux, ctypes.byref(ndim),
                                        ctypes.byref(p64)))
    assert [p64[i] for i in range(ndim.value)] == [5]
    data = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayGetDataNDArray(h, ctypes.byref(data)))
    _check(lib, lib.MXNDArraySyncCheckFormat(h, True))


def test_shared_mem_abi(lib):
    """MXNDArrayGetSharedMemHandle -> MXNDArrayCreateFromSharedMem round
    trip through a POSIX shm segment."""
    src = _make_nd(lib, np.arange(8, dtype=np.float32).reshape(2, 4))
    pid = ctypes.c_int()
    sid = ctypes.c_int()
    _check(lib, lib.MXNDArrayGetSharedMemHandle(src, ctypes.byref(pid),
                                                ctypes.byref(sid)))
    shape = (ctypes.c_uint32 * 2)(2, 4)
    out = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateFromSharedMem(pid, sid, shape, 2, 0,
                                                 ctypes.byref(out)))
    np.testing.assert_array_equal(
        _to_np(lib, out, (2, 4)),
        np.arange(8, dtype=np.float32).reshape(2, 4))
    # the producer owns the segment name: a SECOND consumer can attach
    # the same (pid, id) pair (reference allows repeated attach)
    out2 = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayCreateFromSharedMem(pid, sid, shape, 2, 0,
                                                 ctypes.byref(out2)))
    np.testing.assert_array_equal(
        _to_np(lib, out2, (2, 4)),
        np.arange(8, dtype=np.float32).reshape(2, 4))
    # freeing the producer handle unlinks the name; a new attach fails
    _check(lib, lib.MXNDArrayFree(src))
    assert lib.MXNDArrayCreateFromSharedMem(pid, sid, shape, 2, 0,
                                            ctypes.byref(out2)) != 0


def test_sparse_assembly_via_aux_copy_abi(lib):
    """The canonical sparse-construction sequence (reference csr_matrix):
    create sparse, then SyncCopyFromNDArray dense components into dst aux
    slots (loc=0 indptr, loc=1 indices) and the data array (loc=-1)."""
    h = ctypes.c_void_p()
    shape = (ctypes.c_uint32 * 2)(2, 4)
    aux_types = (ctypes.c_int * 2)(6, 6)
    aux_ndims = (ctypes.c_uint32 * 2)(1, 1)
    aux_shape = (ctypes.c_uint32 * 2)(3, 3)  # indptr len 3, nnz 3
    _check(lib, lib.MXNDArrayCreateSparseEx(
        2, shape, 2, 1, 0, 0, 0, 2, aux_types, aux_ndims, aux_shape,
        ctypes.byref(h)))
    indptr = _make_nd(lib, np.array([0, 2, 3], np.float32))
    indices = _make_nd(lib, np.array([1, 3, 2], np.float32))
    _check(lib, lib.MXNDArraySyncCopyFromNDArray(h, indptr, 0))
    _check(lib, lib.MXNDArraySyncCopyFromNDArray(h, indices, 1))
    data = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayGetDataNDArray(h, ctypes.byref(data)))
    vals = _make_nd(lib, np.array([10., 20., 30.], np.float32))
    _check(lib, lib.MXNDArraySyncCopyFromNDArray(data, vals, -1))
    _check(lib, lib.MXNDArraySyncCheckFormat(h, True))
    # densify through the Python side to verify the assembled contents
    import incubator_mxnet_tpu.capi_impl as impl
    import ctypes as ct
    obj = ct.cast(h, ct.py_object).value
    dense = obj.tostype("default").asnumpy()
    want = np.zeros((2, 4), np.float32)
    want[0, 1], want[0, 3], want[1, 2] = 10., 20., 30.
    np.testing.assert_array_equal(dense, want)


def test_reshape_reverse_abi(lib):
    """MXNDArrayReshape64 reverse=true: wildcards match right-to-left
    (reference mxnet.test_utils reshape semantics: (2,3,5) + (0,-1)
    reverse -> (15,2)... canonical case (2,3,5)+(0,-3) -> (2,15))."""
    x = _make_nd(lib, np.arange(30, dtype=np.float32).reshape(2, 3, 5))
    r = ctypes.c_void_p()
    dims = (ctypes.c_int64 * 2)(0, -3)
    _check(lib, lib.MXNDArrayReshape64(x, 2, dims, True, ctypes.byref(r)))
    ndim = ctypes.c_int()
    p64 = ctypes.POINTER(ctypes.c_int64)()
    _check(lib, lib.MXNDArrayGetShape64(r, ctypes.byref(ndim),
                                        ctypes.byref(p64)))
    assert [p64[i] for i in range(ndim.value)] == [2, 15]


def test_symbol_atomic_compose_abi(lib):
    """MXSymbolCreateAtomicSymbol + MXSymbolCompose (the reference's
    two-step construction), atomic-name reflection, group, shallow copy,
    input symbols."""
    atom = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"4")
    _check(lib, lib.MXSymbolCreateAtomicSymbol(b"FullyConnected", 1, keys,
                                               vals, ctypes.byref(atom)))
    nm = ctypes.c_char_p()
    _check(lib, lib.MXSymbolGetAtomicSymbolName(atom, ctypes.byref(nm)))
    assert nm.value == b"FullyConnected"

    data = ctypes.c_void_p()
    w = ctypes.c_void_p()
    b = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    _check(lib, lib.MXSymbolCreateVariable(b"w", ctypes.byref(w)))
    _check(lib, lib.MXSymbolCreateVariable(b"b", ctypes.byref(b)))
    in_keys = (ctypes.c_char_p * 3)(b"data", b"weight", b"bias")
    in_args = (ctypes.c_void_p * 3)(data, w, b)
    _check(lib, lib.MXSymbolCompose(atom, b"fc0", 3, in_keys, in_args))
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXSymbolListArguments(atom, ctypes.byref(n),
                                          ctypes.byref(arr)))
    assert [arr[i].decode() for i in range(n.value)] == ["data", "w", "b"]

    # GenAtomicSymbolFromSymbol reflects back the head op
    atom2 = ctypes.c_void_p()
    _check(lib, lib.MXGenAtomicSymbolFromSymbol(atom, ctypes.byref(atom2)))
    _check(lib, lib.MXSymbolGetAtomicSymbolName(atom2, ctypes.byref(nm)))
    assert nm.value == b"FullyConnected"

    cp = ctypes.c_void_p()
    _check(lib, lib.MXShallowCopySymbol(atom, ctypes.byref(cp)))
    _check(lib, lib.MXSymbolListArguments(cp, ctypes.byref(n),
                                          ctypes.byref(arr)))
    assert n.value == 3

    grp = ctypes.c_void_p()
    syms = (ctypes.c_void_p * 2)(atom, cp)
    _check(lib, lib.MXSymbolCreateGroup(2, syms, ctypes.byref(grp)))
    _check(lib, lib.MXSymbolGetNumOutputs(grp, ctypes.byref(n)))
    assert n.value == 2

    ins = ctypes.POINTER(ctypes.c_void_p)()
    sz = ctypes.c_int()
    _check(lib, lib.MXSymbolGetInputSymbols(atom, ctypes.byref(ins),
                                            ctypes.byref(sz)))
    assert sz.value == 3

    # MXSymbolGrad is reference-parity unimplemented: must FAIL loudly
    g = ctypes.c_void_p()
    wrt = (ctypes.c_char_p * 1)(b"data")
    assert lib.MXSymbolGrad(atom, 1, wrt, ctypes.byref(g)) != 0


def test_symbol_infer_type_partial_abi(lib):
    import incubator_mxnet_tpu.symbol as sym
    s = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                           num_hidden=4)
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))
    keys = (ctypes.c_char_p * 1)(b"data")
    codes = (ctypes.c_int * 1)(0)
    in_sz = ctypes.c_uint32(); out_sz = ctypes.c_uint32()
    aux_sz = ctypes.c_uint32()
    in_t = ctypes.POINTER(ctypes.c_int)()
    out_t = ctypes.POINTER(ctypes.c_int)()
    aux_t = ctypes.POINTER(ctypes.c_int)()
    comp = ctypes.c_int()
    _check(lib, lib.MXSymbolInferTypePartial(
        h, 1, keys, codes, ctypes.byref(in_sz), ctypes.byref(in_t),
        ctypes.byref(out_sz), ctypes.byref(out_t), ctypes.byref(aux_sz),
        ctypes.byref(aux_t), ctypes.byref(comp)))
    assert in_sz.value == 3 and out_sz.value == 1


def test_executor_simple_bind_monitor_abi(lib):
    """MXExecutorSimpleBindEx allocates arrays; train step through
    Forward/BackwardEx; monitor callback fires per output; Print and
    GetOptimizedSymbol reflect the bound graph."""
    import incubator_mxnet_tpu.symbol as sym
    s = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                           num_hidden=3)
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))

    shape_names = (ctypes.c_char_p * 1)(b"data")
    shape_data = (ctypes.c_int * 2)(2, 5)
    shape_idx = (ctypes.c_uint32 * 2)(0, 2)
    n_in = ctypes.c_uint32(); n_aux = ctypes.c_uint32()
    in_args = ctypes.POINTER(ctypes.c_void_p)()
    arg_grads = ctypes.POINTER(ctypes.c_void_p)()
    auxs = ctypes.POINTER(ctypes.c_void_p)()
    exe = ctypes.c_void_p()
    _check(lib, lib.MXExecutorSimpleBindEx(
        h, 1, 0,                      # dev
        0, None, None, None,          # group2ctx
        0, None, None,                # grad req -> default write
        1, shape_names, shape_data, shape_idx,
        0, None, None,                # dtypes
        0, None, None,                # stypes
        0, None,                      # shared arg names
        None, None, None, None, None, # shared buffer
        ctypes.byref(n_in), ctypes.byref(in_args), ctypes.byref(arg_grads),
        ctypes.byref(n_aux), ctypes.byref(auxs),
        None, ctypes.byref(exe)))
    assert n_in.value == 3
    # fill data/w/b
    xs = [np.random.RandomState(i).rand(*shp).astype(np.float32)
          for i, shp in enumerate([(2, 5), (3, 5), (3,)])]
    for hdl, arr in zip([in_args[i] for i in range(3)], xs):
        _check(lib, lib.MXNDArraySyncCopyFromCPU(
            ctypes.c_void_p(hdl), arr.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_size_t(arr.size)))

    seen = []
    CB = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                          ctypes.c_void_p)
    # the monitor hands the callee an OWNED handle (reference contract)
    cb = CB(lambda name, arr, param: (seen.append(name.decode()),
                                      lib.MXNDArrayFree(
                                          ctypes.c_void_p(arr))))
    _check(lib, lib.MXExecutorSetMonitorCallback(exe, cb, None))

    _check(lib, lib.MXExecutorForward(exe, 1))
    n_out = ctypes.c_uint32()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXExecutorOutputs(exe, ctypes.byref(n_out),
                                      ctypes.byref(outs)))
    got = _to_np(lib, ctypes.c_void_p(outs[0]), (2, 3))
    np.testing.assert_allclose(got, xs[0] @ xs[1].T + xs[2], rtol=1e-5)
    assert seen, "monitor callback never fired"

    og = _make_nd(lib, np.ones((2, 3), np.float32))
    _check(lib, lib.MXExecutorBackwardEx(exe, 1,
                                         (ctypes.c_void_p * 1)(og), 1))
    gw = _to_np(lib, ctypes.c_void_p(arg_grads[1]), (3, 5))
    np.testing.assert_allclose(gw, np.ones((2, 3)).T @ xs[0], rtol=1e-5)

    txt = ctypes.c_char_p()
    _check(lib, lib.MXExecutorPrint(exe, ctypes.byref(txt)))
    assert b"arg" in txt.value
    opt = ctypes.c_void_p()
    _check(lib, lib.MXExecutorGetOptimizedSymbol(exe, ctypes.byref(opt)))
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXSymbolListArguments(opt, ctypes.byref(n),
                                          ctypes.byref(arr)))
    assert n.value == 3


def test_misc_runtime_tail_abi(lib):
    """Numpy-shape mode, bulk size, features, GPU info, creator-handle
    invoke, process-profiler aliases, optimize-for/AMP symbol passes."""
    prev = ctypes.c_int()
    _check(lib, lib.MXSetIsNumpyShape(1, ctypes.byref(prev)))
    cur = ctypes.c_int()
    _check(lib, lib.MXIsNumpyShape(ctypes.byref(cur)))
    assert cur.value == 1
    _check(lib, lib.MXSetIsNumpyShape(prev.value, ctypes.byref(cur)))

    _check(lib, lib.MXRandomSeedContext(7, 1, 0))
    pb = ctypes.c_int()
    _check(lib, lib.MXEngineSetBulkSize(16, ctypes.byref(pb)))

    class Feat(ctypes.Structure):
        _fields_ = [("name", ctypes.c_char_p), ("enabled", ctypes.c_bool)]
    feats = ctypes.POINTER(Feat)()
    n = ctypes.c_size_t()
    _check(lib, lib.MXLibInfoFeatures(ctypes.byref(feats), ctypes.byref(n)))
    names = {feats[i].name.decode() for i in range(n.value)}
    assert n.value > 0 and any("TPU" in x or "XLA" in x for x in names), names

    free_mb = ctypes.c_int(); total_mb = ctypes.c_int()
    _check(lib, lib.MXGetGPUMemoryInformation(0, ctypes.byref(free_mb),
                                              ctypes.byref(total_mb)))

    # creator-handle invoke: list creators, find relu, invoke through it
    nc = ctypes.c_uint32()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXSymbolListAtomicSymbolCreators(ctypes.byref(nc),
                                                     ctypes.byref(creators)))
    relu = None
    for i in range(nc.value):
        if ctypes.cast(creators[i], ctypes.c_char_p).value == b"relu":
            relu = creators[i]
            break
    assert relu is not None
    x = _make_nd(lib, np.array([-1., 2., -3.], np.float32))
    n_out = ctypes.c_int(0)
    outs = ctypes.POINTER(ctypes.c_void_p)()
    stypes = ctypes.POINTER(ctypes.c_int)()
    _check(lib, lib.MXImperativeInvokeEx(
        ctypes.c_void_p(relu), 1, (ctypes.c_void_p * 1)(x),
        ctypes.byref(n_out), ctypes.byref(outs), 0, None, None,
        ctypes.byref(stypes)))
    got = _to_np(lib, ctypes.c_void_p(outs[0]), (3,))
    np.testing.assert_array_equal(got, [0., 2., 0.])
    assert stypes[0] == 0

    # process-profiler aliases ride the per-worker profiler
    keys = (ctypes.c_char_p * 1)(b"profile_all")
    vals = (ctypes.c_char_p * 1)(b"1")
    _check(lib, lib.MXSetProcessProfilerConfig(1, keys, vals, None))
    _check(lib, lib.MXSetProcessProfilerState(1, 0, None))
    _check(lib, lib.MXProcessProfilePause(1, 0, None))
    _check(lib, lib.MXProcessProfilePause(0, 0, None))
    _check(lib, lib.MXSetProcessProfilerState(0, 0, None))

    # AMP + backend passes return usable symbols
    import incubator_mxnet_tpu.symbol as sym
    s = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                           num_hidden=4)
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))
    amp = ctypes.c_void_p()
    tgt = (ctypes.c_int * 1)(1)
    _check(lib, lib.MXReducePrecisionSymbol(
        h, ctypes.byref(amp), 0, None, 0, None, tgt, 0,
        0, 0, 0, 0, 0, 0,
        None, None, None, None, None, None, None, None, None))
    opt = ctypes.c_void_p()
    _check(lib, lib.MXOptimizeForBackend(
        h, b"xla", 1, ctypes.byref(opt), 0, None, 0, None, 0, None, None,
        None, None, None, None, None, None))
    na = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXSymbolListArguments(opt, ctypes.byref(na),
                                          ctypes.byref(arr)))
    assert na.value == 3

    # data-iter reflection
    nm = ctypes.c_char_p(); desc = ctypes.c_char_p()
    nargs = ctypes.c_uint32()
    an = ctypes.POINTER(ctypes.c_char_p)()
    at = ctypes.POINTER(ctypes.c_char_p)()
    ad = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.MXDataIterGetIterInfo(
        ctypes.c_char_p(b"MNISTIter"), ctypes.byref(nm), ctypes.byref(desc),
        ctypes.byref(nargs), ctypes.byref(an), ctypes.byref(at),
        ctypes.byref(ad)))
    assert nm.value == b"MNISTIter"

    # ps-env + dead-node + exit-barrier surface
    _check(lib, lib.MXInitPSEnv(1, (ctypes.c_char_p * 1)(b"DMLC_ROLE"),
                                (ctypes.c_char_p * 1)(b"worker")))
    kv = ctypes.c_void_p()
    _check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    dead = ctypes.c_int(-1)
    _check(lib, lib.MXKVStoreGetNumDeadNode(kv, 0, ctypes.byref(dead), 1))
    assert dead.value == 0
    _check(lib, lib.MXKVStoreSetBarrierBeforeExit(kv, 1))
    _check(lib, lib.MXKVStoreFree(kv))


def test_abi_tail_batch(lib):
    """Bind/SimpleBind legacy+64 aliases, InferShapeEx/64 family,
    MXGetFunction, PullWithSparse, SetUpdaterEx str keys, cached-op hook,
    dlpack round trip, rtc/tvm build-parity errors."""
    import incubator_mxnet_tpu.symbol as sym
    s = sym.FullyConnected(sym.var("data"), sym.var("w"), sym.var("b"),
                           num_hidden=3)
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))

    # InferShapeEx (int data)
    keys = (ctypes.c_char_p * 1)(b"data")
    ind = (ctypes.c_uint32 * 2)(0, 2)
    data = (ctypes.c_int * 2)(2, 5)
    isz = ctypes.c_uint32(); osz = ctypes.c_uint32(); asz = ctypes.c_uint32()
    indim = ctypes.POINTER(ctypes.c_int)()
    ondim = ctypes.POINTER(ctypes.c_int)()
    andim = ctypes.POINTER(ctypes.c_int)()
    idata = ctypes.POINTER(ctypes.POINTER(ctypes.c_int))()
    odata = ctypes.POINTER(ctypes.POINTER(ctypes.c_int))()
    adata = ctypes.POINTER(ctypes.POINTER(ctypes.c_int))()
    comp = ctypes.c_int()
    _check(lib, lib.MXSymbolInferShapeEx(
        h, 1, keys, ind, data, ctypes.byref(isz), ctypes.byref(indim),
        ctypes.byref(idata), ctypes.byref(osz), ctypes.byref(ondim),
        ctypes.byref(odata), ctypes.byref(asz), ctypes.byref(andim),
        ctypes.byref(adata), ctypes.byref(comp)))
    assert comp.value == 1 and osz.value == 1
    assert [odata[0][d] for d in range(ondim[0])] == [2, 3]

    # InferShape64 (int64 everywhere)
    ind64 = (ctypes.c_int64 * 2)(0, 2)
    data64 = (ctypes.c_int64 * 2)(2, 5)
    isz64 = ctypes.c_size_t(); osz64 = ctypes.c_size_t()
    asz64 = ctypes.c_size_t()
    i64 = ctypes.POINTER(ctypes.POINTER(ctypes.c_int64))()
    o64 = ctypes.POINTER(ctypes.POINTER(ctypes.c_int64))()
    a64 = ctypes.POINTER(ctypes.POINTER(ctypes.c_int64))()
    _check(lib, lib.MXSymbolInferShape64(
        h, 1, keys, ind64, data64, ctypes.byref(isz64), ctypes.byref(indim),
        ctypes.byref(i64), ctypes.byref(osz64), ctypes.byref(ondim),
        ctypes.byref(o64), ctypes.byref(asz64), ctypes.byref(andim),
        ctypes.byref(a64), ctypes.byref(comp)))
    assert osz64.value == 1
    assert [o64[0][d] for d in range(ondim[0])] == [2, 3]

    # legacy SimpleBind (uint32 shapes) through the Ex path
    shape_names = (ctypes.c_char_p * 1)(b"data")
    shape_data = (ctypes.c_uint32 * 2)(2, 5)
    shape_idx = (ctypes.c_uint32 * 2)(0, 2)
    n_in = ctypes.c_uint32(); n_aux = ctypes.c_uint32()
    in_args = ctypes.POINTER(ctypes.c_void_p)()
    arg_grads = ctypes.POINTER(ctypes.c_void_p)()
    auxs = ctypes.POINTER(ctypes.c_void_p)()
    exe = ctypes.c_void_p()
    _check(lib, lib.MXExecutorSimpleBind(
        h, 1, 0, 0, None, None, None, 0, None, None,
        1, shape_names, shape_data, shape_idx,
        0, None, None, 0, None, None, 0, None,
        None, None, None, None, None,
        ctypes.byref(n_in), ctypes.byref(in_args), ctypes.byref(arg_grads),
        ctypes.byref(n_aux), ctypes.byref(auxs), None, ctypes.byref(exe)))
    assert n_in.value == 3

    # MXGetFunction: valid + invalid names
    fh = ctypes.c_void_p()
    _check(lib, lib.MXGetFunction(b"relu", ctypes.byref(fh)))
    assert ctypes.cast(fh, ctypes.c_char_p).value == b"relu"
    assert lib.MXGetFunction(b"not_a_real_op_name", ctypes.byref(fh)) != 0

    # PullWithSparse over a local store
    kv = ctypes.c_void_p()
    _check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    ikeys = (ctypes.c_int * 1)(3)
    _check(lib, lib.MXKVStoreInit(
        kv, 1, ikeys,
        (ctypes.c_void_p * 1)(_make_nd(lib, np.full(4, 2.0, np.float32)))))
    out = _make_nd(lib, np.zeros(4, np.float32))
    _check(lib, lib.MXKVStorePullWithSparse(
        kv, 1, ikeys, (ctypes.c_void_p * 1)(out), 0, True))
    np.testing.assert_array_equal(_to_np(lib, out, (4,)),
                                  np.full(4, 2.0, np.float32))

    # SetUpdaterEx: int keys hit the int updater
    hits = []
    UPD = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                           ctypes.c_void_p, ctypes.c_void_p)
    SUPD = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                            ctypes.c_void_p, ctypes.c_void_p)
    def _rec_free(tag, k, r, l):
        hits.append((tag, k))
        lib.MXNDArrayFree(ctypes.c_void_p(r))
        lib.MXNDArrayFree(ctypes.c_void_p(l))
    upd = UPD(lambda k, r, l, p: _rec_free("int", k, r, l))
    supd = SUPD(lambda k, r, l, p: _rec_free("str", k, r, l))
    _check(lib, lib.MXKVStoreSetUpdaterEx(kv, upd, supd, None))
    g = _make_nd(lib, np.ones(4, np.float32))
    _check(lib, lib.MXKVStorePush(kv, 1, ikeys,
                                  (ctypes.c_void_p * 1)(g), 0))
    assert ("int", 3) in hits
    _check(lib, lib.MXKVStoreFree(kv))

    # cached-op monitor hook fires on invoke
    co = ctypes.c_void_p()
    _check(lib, lib.MXCreateCachedOp(h, ctypes.byref(co)))
    seen = []
    HOOK = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_char_p,
                            ctypes.c_void_p)
    hook = HOOK(lambda name, opr, arr: (seen.append(name.decode()),
                                        lib.MXNDArrayFree(
                                            ctypes.c_void_p(arr))))
    _check(lib, lib.MXCachedOpRegisterOpHook(co, hook, False))
    xs = [np.random.RandomState(i).rand(*shp).astype(np.float32)
          for i, shp in enumerate([(2, 5), (3, 5), (3,)])]
    handles = (ctypes.c_void_p * 3)(*[_make_nd(lib, a) for a in xs])
    n_out = ctypes.c_int(0)
    outs = ctypes.POINTER(ctypes.c_void_p)()
    _check(lib, lib.MXInvokeCachedOp(co, 3, handles, ctypes.byref(n_out),
                                     ctypes.byref(outs)))
    assert seen == ["output0"]

    # dlpack round trip
    src = _make_nd(lib, np.arange(6, dtype=np.float32).reshape(2, 3))
    dlp = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayToDLPack(src, ctypes.byref(dlp)))
    back = ctypes.c_void_p()
    _check(lib, lib.MXNDArrayFromDLPack(dlp, ctypes.byref(back)))
    np.testing.assert_array_equal(
        _to_np(lib, back, (2, 3)),
        np.arange(6, dtype=np.float32).reshape(2, 3))
    _check(lib, lib.MXNDArrayCallDLPackDeleter(dlp))

    # rtc / tvm: faithful built-without-support errors
    assert lib.MXRtcFree(None) != 0
    assert lib.MXLoadTVMOp(b"/nonexistent.so") != 0


def test_set_calib_table_abi(lib):
    """MXQuantizeSymbol -> MXSetCalibTableToQuantizedSymbol re-runs the
    quantization pass with ranges attached to requantize nodes."""
    import incubator_mxnet_tpu.symbol as sym
    s = sym.Convolution(sym.var("data"), sym.var("w"), None, kernel=(1, 1),
                        num_filter=4, no_bias=True)
    s = sym.Activation(s, act_type="relu")
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))
    q = ctypes.c_void_p()
    _check(lib, lib.MXQuantizeSymbol(h, ctypes.byref(q), 0, None, 0, None,
                                     b"int8"))
    names = (ctypes.c_char_p * 2)(b"data", b"convolution0_output")
    lows = (ctypes.c_float * 2)(-3.0, -6.0)
    highs = (ctypes.c_float * 2)(3.0, 6.0)
    out = ctypes.c_void_p()
    _check(lib, lib.MXSetCalibTableToQuantizedSymbol(
        q, 2, names, lows, highs, ctypes.byref(out)))
    js = ctypes.c_char_p()
    _check(lib, lib.MXSymbolSaveToJSON(out, ctypes.byref(js)))
    # calibrated ranges pin the quantize nodes (no data-dependent rescan)
    assert b"min_calib_range" in js.value


def test_kvstore_server_surface_abi(lib):
    """MXKVStoreRunServer installs the command controller (no separate
    server process: the store itself is the server role) and
    MXKVStoreSendCommmandToServers dispatches to it."""
    kv = ctypes.c_void_p()
    _check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    got = []
    CTRL = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_char_p,
                            ctypes.c_void_p)
    ctrl = CTRL(lambda head, body, p: got.append((head, body.decode())))
    _check(lib, lib.MXKVStoreRunServer(kv, ctrl, None))
    _check(lib, lib.MXKVStoreSendCommmandToServers(kv, 7, b"set_lr:0.01"))
    assert got == [(7, "set_lr:0.01")]
    _check(lib, lib.MXKVStoreFree(kv))


class _MXCallbackList(ctypes.Structure):
    _fields_ = [("num_callbacks", ctypes.c_int),
                ("callbacks", ctypes.POINTER(
                    ctypes.CFUNCTYPE(ctypes.c_int))),
                ("contexts", ctypes.POINTER(ctypes.c_void_p))]


def test_custom_op_register_abi(lib):
    """MXCustomOpRegister: the full struct-of-callbacks protocol
    (c_api.h:153-206, custom.cc AttrParser/List/InferShape) — a C
    'library' (ctypes function pointers) registers op 'cdouble'
    (y = 2x), and nd.Custom(op_type='cdouble') runs fwd+bwd through
    the C callbacks."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd

    keep = []  # keep every callback/static buffer alive for the test

    RAWFN = ctypes.CFUNCTYPE(ctypes.c_int)
    LIST = ctypes.CFUNCTYPE(ctypes.c_int,
                            ctypes.POINTER(ctypes.POINTER(
                                ctypes.c_char_p)), ctypes.c_void_p)
    SHAPE = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_int,
                             ctypes.POINTER(ctypes.c_int),
                             ctypes.POINTER(ctypes.POINTER(ctypes.c_int)),
                             ctypes.c_void_p)
    DEP = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                           ctypes.POINTER(ctypes.c_int),
                           ctypes.POINTER(ctypes.c_int),
                           ctypes.POINTER(ctypes.c_int),
                           ctypes.POINTER(ctypes.POINTER(ctypes.c_int)),
                           ctypes.c_void_p)
    CREATE = ctypes.CFUNCTYPE(
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint)),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(_MXCallbackList), ctypes.c_void_p)
    FB = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_int,
                          ctypes.POINTER(ctypes.c_void_p),
                          ctypes.POINTER(ctypes.c_int),
                          ctypes.POINTER(ctypes.c_int), ctypes.c_int,
                          ctypes.c_void_p)
    CREATOR = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
                               ctypes.POINTER(ctypes.c_char_p),
                               ctypes.POINTER(ctypes.c_char_p),
                               ctypes.POINTER(_MXCallbackList))

    def make_list(names):
        arr = (ctypes.c_char_p * (len(names) + 1))(
            *[n.encode() for n in names], None)
        keep.append(arr)

        @LIST
        def fn(out, _state):
            out[0] = ctypes.cast(arr, ctypes.POINTER(ctypes.c_char_p))
            return 1
        keep.append(fn)
        return fn

    list_args = make_list(["data"])
    list_outs = make_list(["output"])
    list_aux = make_list([])

    @SHAPE
    def infer_shape(num_input, ndims, shapes, _state):
        # total = 1 arg + 1 out; output shape := input shape
        assert num_input == 2
        ndims[1] = ndims[0]
        shapes[1] = shapes[0]
        return 1
    keep.append(infer_shape)

    @DEP
    def bwd_dep(out_grad, in_data, out_data, num_deps, rdeps, _state):
        deps = (ctypes.c_int * 2)(out_grad[0], in_data[0])
        keep.append(deps)
        num_deps[0] = 2
        rdeps[0] = ctypes.cast(deps, ctypes.POINTER(ctypes.c_int))
        return 1
    keep.append(bwd_dep)

    def _nd_scale(lib, handle, factor, out_handle):
        """Reads `handle` via the C API and writes factor*x into
        out_handle THROUGH the MXNDArrayGetData pointer with no explicit
        WaitToRead — the canonical reference custom-op style; the bridge
        must flush the host buffer when the callback returns."""
        ndim = ctypes.c_uint32()
        pshape = ctypes.POINTER(ctypes.c_uint32)()
        _check(lib, lib.MXNDArrayGetShape(handle, ctypes.byref(ndim),
                                          ctypes.byref(pshape)))
        size = 1
        for i in range(ndim.value):
            size *= pshape[i]
        buf = np.zeros(size, np.float32)
        _check(lib, lib.MXNDArraySyncCopyToCPU(
            handle, buf.ctypes.data_as(ctypes.c_void_p), size))
        buf *= factor
        ptr = ctypes.c_void_p()
        _check(lib, lib.MXNDArrayGetData(out_handle, ctypes.byref(ptr)))
        ctypes.memmove(ptr, buf.ctypes.data_as(ctypes.c_void_p),
                       buf.nbytes)

    def _free_all(size, ptrs):
        # handle ownership transferred to this callback (reference ABI:
        # per-callback NDArrays, custom.cc ForwardEx) — free every one
        for i in range(size):
            _check(lib, lib.MXNDArrayFree(ctypes.c_void_p(ptrs[i])))

    @FB
    def forward(size, ptrs, tags, reqs, is_train, _state):
        ins = [ptrs[i] for i in range(size) if tags[i] == 0]
        outs = [ptrs[i] for i in range(size) if tags[i] == 1]
        _nd_scale(lib, ctypes.c_void_p(ins[0]), 2.0,
                  ctypes.c_void_p(outs[0]))
        _free_all(size, ptrs)
        return 1
    keep.append(forward)

    @FB
    def backward(size, ptrs, tags, reqs, is_train, _state):
        ogs = [ptrs[i] for i in range(size) if tags[i] == 3]
        igs = [ptrs[i] for i in range(size) if tags[i] == 2]
        _nd_scale(lib, ctypes.c_void_p(ogs[0]), 2.0,
                  ctypes.c_void_p(igs[0]))
        _free_all(size, ptrs)
        return 1
    keep.append(backward)

    @CREATE
    def create_operator(ctx, num_inputs, shapes, ndims, dtypes, ret,
                        _state):
        cbs = (ctypes.CFUNCTYPE(ctypes.c_int) * 3)(
            ctypes.cast(None, RAWFN), ctypes.cast(forward, RAWFN),
            ctypes.cast(backward, RAWFN))
        ctxs = (ctypes.c_void_p * 3)(None, None, None)
        keep.extend((cbs, ctxs))
        ret[0].num_callbacks = 3
        ret[0].callbacks = ctypes.cast(
            cbs, ctypes.POINTER(ctypes.CFUNCTYPE(ctypes.c_int)))
        ret[0].contexts = ctypes.cast(ctxs,
                                      ctypes.POINTER(ctypes.c_void_p))
        return 1
    keep.append(create_operator)

    @CREATOR
    def creator(op_type, num_kwargs, keys, vals, ret):
        # prop callback table (order = CustomOpPropCallbacks)
        cbs = (ctypes.CFUNCTYPE(ctypes.c_int) * 8)(
            ctypes.cast(None, RAWFN),            # PropDelete
            ctypes.cast(list_args, RAWFN),
            ctypes.cast(list_outs, RAWFN),
            ctypes.cast(list_aux, RAWFN),
            ctypes.cast(infer_shape, RAWFN),
            ctypes.cast(bwd_dep, RAWFN),
            ctypes.cast(create_operator, RAWFN),
            ctypes.cast(None, RAWFN))            # InferType (absent)
        ctxs = (ctypes.c_void_p * 8)(*([None] * 8))
        keep.extend((cbs, ctxs))
        ret[0].num_callbacks = 8
        ret[0].callbacks = ctypes.cast(
            cbs, ctypes.POINTER(ctypes.CFUNCTYPE(ctypes.c_int)))
        ret[0].contexts = ctypes.cast(ctxs,
                                      ctypes.POINTER(ctypes.c_void_p))
        return 1
    keep.append(creator)

    _check(lib, lib.MXCustomOpRegister(b"cdouble", creator))

    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="cdouble")
        y.backward(nd.ones_like(y))
    np.testing.assert_allclose(y.asnumpy(), 2 * x.asnumpy())
    np.testing.assert_allclose(x.grad.asnumpy(),
                               np.full((2, 3), 2.0, np.float32))


def test_custom_function_record_abi(lib):
    """MXCustomFunctionRecord (c_api_function.cc:186): graft a C backward
    onto imperatively computed outputs; backward receives
    [ograds.., igrads..] and fills igrads."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd

    keep = []
    RAWFN = ctypes.CFUNCTYPE(ctypes.c_int)
    BWD = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_int, ctypes.c_int,
                           ctypes.POINTER(ctypes.c_void_p),
                           ctypes.POINTER(ctypes.c_int), ctypes.c_int,
                           ctypes.c_void_p)

    @BWD
    def backward(num_ograds, num_igrads, ptrs, reqs, is_train, _state):
        assert num_ograds == 1 and num_igrads == 1
        og, ig = ctypes.c_void_p(ptrs[0]), ctypes.c_void_p(ptrs[1])
        buf = np.zeros(4, np.float32)
        _check(lib, lib.MXNDArraySyncCopyToCPU(
            og, buf.ctypes.data_as(ctypes.c_void_p), 4))
        buf *= 3.0  # d/dx of the 'pretend' function y = 3x
        _check(lib, lib.MXNDArraySyncCopyFromCPU(
            ig, buf.ctypes.data_as(ctypes.c_void_p), 4))
        # ownership of both handles transferred here; free per the ABI
        _check(lib, lib.MXNDArrayFree(og))
        _check(lib, lib.MXNDArrayFree(ig))
        return 1
    keep.append(backward)

    x = nd.array(np.arange(4, dtype=np.float32))
    x.attach_grad()
    with autograd.record():
        y = x * 3.0  # computed imperatively; C function claims its grad

        cbs = (ctypes.CFUNCTYPE(ctypes.c_int) * 2)(
            ctypes.cast(backward, RAWFN), ctypes.cast(None, RAWFN))
        ctxs = (ctypes.c_void_p * 2)(None, None)
        keep.extend((cbs, ctxs))
        cblist = _MXCallbackList(
            2, ctypes.cast(cbs,
                           ctypes.POINTER(ctypes.CFUNCTYPE(ctypes.c_int))),
            ctypes.cast(ctxs, ctypes.POINTER(ctypes.c_void_p)))
        ins = (ctypes.c_void_p * 1)(_py_handle(x))
        outs = (ctypes.c_void_p * 1)(_py_handle(y))
        _check(lib, lib.MXCustomFunctionRecord(1, ins, 1, outs,
                                               ctypes.byref(cblist)))
        y.backward(nd.ones_like(y))
    np.testing.assert_allclose(x.grad.asnumpy(),
                               np.full(4, 3.0, np.float32))


def test_subgraph_test_hooks_abi(lib):
    """c_api_test.h: MXBuildSubgraphByOpNames partitions by the given op
    list; Set/RemoveSubgraphPropertyOpNames override a property's op set
    (SubgraphPropertyOpNameSet semantics)."""
    import incubator_mxnet_tpu.symbol as sym

    s = sym.sin(sym.exp(sym.var("data")) + sym.var("b"))
    h = ctypes.c_void_p()
    _check(lib, lib.MXSymbolCreateFromJSON(s.tojson().encode(),
                                           ctypes.byref(h)))
    names = (ctypes.c_char_p * 2)(b"exp", b"elemwise_add")
    out = ctypes.c_void_p()
    _check(lib, lib.MXBuildSubgraphByOpNames(h, b"testprop", 2, names,
                                             ctypes.byref(out)))
    js = ctypes.c_char_p()
    _check(lib, lib.MXSymbolSaveToJSON(out, ctypes.byref(js)))
    first = bytes(js.value)  # SaveToJSON reuses a thread-local buffer
    assert b"subgraph" in first, first

    # the override hook replaces the op set for that property name
    only_sin = (ctypes.c_char_p * 1)(b"sin",)
    _check(lib, lib.MXSetSubgraphPropertyOpNames(b"testprop", 1, only_sin))
    out2 = ctypes.c_void_p()
    _check(lib, lib.MXBuildSubgraphByOpNames(h, b"testprop", 2, names,
                                             ctypes.byref(out2)))
    js2 = ctypes.c_char_p()
    _check(lib, lib.MXSymbolSaveToJSON(out2, ctypes.byref(js2)))
    second = bytes(js2.value)
    _check(lib, lib.MXRemoveSubgraphPropertyOpNames(b"testprop"))
    assert second != first  # different partitioning under the override
    # sin is a TOP-LEVEL node in the first partition but moves inside
    # the subgraph (escaped, embedded JSON) under the {"sin"} override
    assert b'"op": "sin"' in first
    assert b'"op": "sin"' not in second
