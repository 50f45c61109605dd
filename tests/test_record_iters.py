"""Record-file data pipeline: ImageRecordIter / MNISTIter / LibSVMIter /
im2rec (reference: src/io/iter_image_recordio_2.cc, iter_mnist.cc,
iter_libsvm.cc, tools/im2rec.py)."""
import gzip
import os
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import io as mio
from incubator_mxnet_tpu.recordio import (IRHeader, MXIndexedRecordIO, pack,
                                          pack_img)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_rec(tmp_path, n=64, hw=32, label_fn=lambda i: i % 10):
    prefix = str(tmp_path / "data")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, (hw, hw, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(label_fn(i)), i, 0), img,
                                  img_fmt=".png"))
    rec.close()
    return prefix


def test_image_record_iter_basic(tmp_path):
    prefix = _write_rec(tmp_path, n=30, hw=40)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 32, 32), batch_size=8,
                             shuffle=True, rand_mirror=True,
                             preprocess_threads=2, prefetch_buffer=2)
    batches = list(it)
    # 30 records, batch 8, round_batch pads the tail
    assert len(batches) == 4
    assert batches[0].data[0].shape == (8, 3, 32, 32)
    assert batches[0].label[0].shape == (8,)
    assert batches[-1].pad == 2
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    assert set(labels.astype(int)) <= set(range(10))
    # epoch 2 after reset
    it.reset()
    assert len(list(it)) == 4
    it.close()


def test_image_record_iter_sharding(tmp_path):
    prefix = _write_rec(tmp_path, n=32)
    seen = []
    for part in range(2):
        it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                                 path_imgidx=prefix + ".idx",
                                 data_shape=(3, 32, 32), batch_size=16,
                                 part_index=part, num_parts=2)
        b = next(it)
        seen.append(set(b.label[0].asnumpy().astype(int)))
        it.close()
    # round-robin shard: parts see disjoint record sets (labels = i % 10
    # collide, so compare via count: each part gets 16 records)
    assert all(len(s) > 0 for s in seen)


def test_image_record_iter_normalization(tmp_path):
    prefix = _write_rec(tmp_path, n=8)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 32, 32), batch_size=8,
                             mean_r=127.5, mean_g=127.5, mean_b=127.5,
                             std_r=127.5, std_g=127.5, std_b=127.5)
    d = next(it).data[0].asnumpy()
    assert -1.1 <= d.min() and d.max() <= 1.1
    it.close()


def test_image_record_iter_throughput(tmp_path):
    """The pipeline must sustain more img/s than the bench's training rate
    (the round-2 review's 'done' bar) — measured here with tiny 32x32 PNGs on CPU."""
    prefix = _write_rec(tmp_path, n=256)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 32, 32), batch_size=64,
                             shuffle=True, preprocess_threads=4,
                             prefetch_buffer=4)
    list(it)  # warm epoch
    it.reset()
    t0 = time.time()
    n = sum(b.data[0].shape[0] for b in it)
    dt = time.time() - t0
    rate = n / dt
    it.close()
    assert rate > 500, "record pipeline too slow: %.0f img/s" % rate


def test_mnist_iter(tmp_path):
    # synthesize a tiny idx-format MNIST pair (gzip)
    n, hw = 50, 28
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (n, hw, hw), dtype=np.uint8)
    labs = rng.randint(0, 10, (n,)).astype(np.uint8)
    ip = str(tmp_path / "images-idx3-ubyte.gz")
    lp = str(tmp_path / "labels-idx1-ubyte.gz")
    with gzip.open(ip, "wb") as f:
        f.write(struct.pack(">IIII", 0x803, n, hw, hw) + imgs.tobytes())
    with gzip.open(lp, "wb") as f:
        f.write(struct.pack(">II", 0x801, n) + labs.tobytes())

    it = mio.MNISTIter(image=ip, label=lp, batch_size=10, shuffle=False)
    b = next(it)
    assert b.data[0].shape == (10, 1, 28, 28)
    assert float(b.data[0].asnumpy().max()) <= 1.0
    np.testing.assert_array_equal(b.label[0].asnumpy().astype(int), labs[:10])
    # flat mode
    it2 = mio.MNISTIter(image=ip, label=lp, batch_size=10, flat=True,
                        shuffle=False)
    assert next(it2).data[0].shape == (10, 784)


def test_libsvm_iter(tmp_path):
    path = str(tmp_path / "data.libsvm")
    with open(path, "w") as f:
        f.write("1 0:1.5 3:2.0\n")
        f.write("0 1:0.5\n")
        f.write("1 2:3.0 4:1.0\n")
        f.write("0 0:2.5\n")
    it = mio.LibSVMIter(data_libsvm=path, data_shape=(5,), batch_size=2)
    b1 = next(it)
    dense = b1.data[0].asnumpy() if hasattr(b1.data[0], "asnumpy") else None
    assert dense.shape == (2, 5)
    np.testing.assert_allclose(dense[0], [1.5, 0, 0, 2.0, 0])
    np.testing.assert_allclose(dense[1], [0, 0.5, 0, 0, 0])
    np.testing.assert_array_equal(b1.label[0].asnumpy(), [1, 0])
    b2 = next(it)
    assert b2.pad == 0
    with pytest.raises(StopIteration):
        next(it)


def test_im2rec_roundtrip(tmp_path):
    from PIL import Image

    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        rng = np.random.RandomState(hash(cls) % 2**31)
        for i in range(4):
            arr = rng.randint(0, 255, (48, 48, 3), dtype=np.uint8)
            Image.fromarray(arr).save(root / cls / ("%d.png" % i))
    prefix = str(tmp_path / "out")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
                    prefix, str(root), "--list"], check=True, env=env)
    assert os.path.exists(prefix + ".lst")
    subprocess.run([sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
                    prefix, str(root), "--encoding", ".png"], check=True,
                   env=env)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 32, 32), batch_size=4)
    b = next(it)
    assert b.data[0].shape == (4, 3, 32, 32)
    labels = set()
    it.reset()
    for batch in it:
        labels |= set(batch.label[0].asnumpy().astype(int))
    assert labels == {0, 1}
    it.close()


def test_image_record_iter_tiny_shard_pads_fully(tmp_path):
    """Regression: a shard smaller than batch_size must wrap repeatedly —
    no uninitialized rows in the padded batch."""
    prefix = _write_rec(tmp_path, n=3, label_fn=lambda i: i)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 32, 32), batch_size=8)
    b = next(it)
    assert b.pad == 5
    labels = b.label[0].asnumpy().astype(int)
    assert set(labels) == {0, 1, 2}  # every row is a real record
    # stock protocol: iter_next + getdata
    it.reset()
    seen = 0
    while it.iter_next():
        assert it.getdata()[0].shape == (8, 3, 32, 32)
        seen += 1
    assert seen == 1
    it.close()


def test_uint8_iter_and_train_step_promotion(tmp_path):
    """ImageRecordUInt8Iter emits raw NCHW uint8 (no host normalize) and
    the fused train step promotes uint8 inputs to the compute dtype
    (iter_image_recordio_2.cc ImageRecordUInt8Iter semantics)."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.io import ImageRecordUInt8Iter
    from incubator_mxnet_tpu.parallel import make_train_step
    from incubator_mxnet_tpu.recordio import (IRHeader, MXIndexedRecordIO,
                                              pack_img)

    prefix = str(tmp_path / "u8")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(32):
        img = rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i % 4), i, 0), img,
                                  img_fmt=".npy"))
    rec.close()

    it = ImageRecordUInt8Iter(path_imgrec=prefix + ".rec",
                              path_imgidx=prefix + ".idx",
                              data_shape=(3, 16, 16), batch_size=8,
                              preprocess_threads=2, prefetch_buffer=2)
    batch = next(it)
    x = batch.data[0]
    assert x.dtype == np.uint8 and x.shape == (8, 3, 16, 16)

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.Flatten(),
            gluon.nn.Dense(4))
    net.initialize()
    net(nd.zeros((1, 3, 16, 16)))  # materialize deferred params
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           optimizer="sgd", learning_rate=0.1,
                           compute_dtype="bfloat16")
    loss = step(x, batch.label[0])
    assert np.isfinite(float(loss.asscalar()))
    it.close()


def test_record_iter_review_pins(tmp_path):
    """Pins for the review findings: 1-channel shapes, non-uint8 payload
    preservation, uint8-iter kwarg rejection, default-dtype promotion."""
    import numpy as np

    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.io import (ImageRecordIter,
                                        ImageRecordUInt8Iter)
    from incubator_mxnet_tpu.parallel import make_train_step
    from incubator_mxnet_tpu.recordio import (IRHeader, MXIndexedRecordIO,
                                              pack_img)

    # 1-channel data_shape keeps 1 channel through the batch normalize
    prefix = str(tmp_path / "gray")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = rng.randint(0, 255, (8, 8, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, 0.0, i, 0), img,
                                  img_fmt=".npy"))
    rec.close()
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx", data_shape=(1, 8, 8),
                         batch_size=4, preprocess_threads=1,
                         prefetch_buffer=1)
    b = next(it)
    assert b.data[0].shape == (4, 1, 8, 8)
    it.close()

    # float payloads outside [0,255] survive the float iterator untouched
    prefix = str(tmp_path / "floats")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    arr = (rng.rand(8, 8, 3).astype(np.float32) * 1000.0) - 500.0
    rec.write_idx(0, pack_img(IRHeader(0, 0.0, 0, 0), arr, img_fmt=".npy"))
    rec.close()
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx", data_shape=(3, 8, 8),
                         batch_size=1, preprocess_threads=1,
                         prefetch_buffer=1, shuffle=False, rand_mirror=False)
    b = next(it)
    np.testing.assert_allclose(b.data[0].asnumpy()[0],
                               arr.transpose(2, 0, 1), rtol=1e-5)
    it.close()

    # raw-bytes iterator rejects normalization kwargs instead of silently
    # ignoring them
    import pytest as _pytest
    with _pytest.raises(ValueError):
        ImageRecordUInt8Iter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 8, 8), batch_size=1,
                             mean_r=123.0)

    # uint8 batches work with the DEFAULT train step (no compute_dtype)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(2, 3, padding=1), gluon.nn.Flatten(),
            gluon.nn.Dense(2))
    net.initialize()
    net(nd.zeros((1, 3, 8, 8)))
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           optimizer="sgd", learning_rate=0.01)
    x8 = nd.array(np.zeros((2, 3, 8, 8), np.uint8))
    loss = step(x8, nd.zeros((2,)))
    assert np.isfinite(float(loss.asscalar()))


def test_image_record_iter_decode_runs_on_pool_threads(tmp_path):
    """The decode work must execute ON the preprocess_threads pool (not
    the producer thread), i.e. the architecture scales by adding pool
    workers exactly like the reference's iter_image_recordio_2.cc:28-76
    — on a multi-core host the pool IS the scaling mechanism (measured
    by tools/io_thread_scaling.py)."""
    import threading

    from incubator_mxnet_tpu.io import record_iter as ri

    prefix = _write_rec(tmp_path, n=24, hw=32)
    seen = set()
    orig = ri.ImageRecordIter._decode_one

    def spy(self, *a, **k):
        seen.add(threading.current_thread().name)
        return orig(self, *a, **k)

    ri.ImageRecordIter._decode_one = spy
    try:
        it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                                 path_imgidx=prefix + ".idx",
                                 data_shape=(3, 32, 32), batch_size=8,
                                 preprocess_threads=3, prefetch_buffer=2)
        for _ in it:
            pass
    finally:
        ri.ImageRecordIter._decode_one = orig
    # every decode ran on a ThreadPoolExecutor worker; with >= 2 distinct
    # workers observed the fan-out is real, not serialized on one thread
    assert seen and all("ThreadPoolExecutor" in n for n in seen), seen
    assert len(seen) >= 2, "decode never fanned out: %s" % seen


def test_image_record_iter_per_image_decode_cost(tmp_path):
    """Records the per-image decode+augment cost the thread-scaling
    model divides by (PERF.md 'Recordio-fed training'): a regression
    guard, not a benchmark — the bound is ~6x the measured 1.4 ms/img
    to stay robust on loaded CI hosts."""
    prefix = str(tmp_path / "jpg")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(96):
        img = rng.randint(0, 255, (224, 224, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i % 10), i, 0), img,
                                  quality=90, img_fmt=".jpg"))
    rec.close()
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 224, 224), batch_size=32,
                             preprocess_threads=1, prefetch_buffer=2)
    next(it)  # pipeline warm
    best = float("inf")
    t0 = time.perf_counter()
    for b in it:
        t1 = time.perf_counter()
        best = min(best, (t1 - t0) / b.data[0].shape[0] * 1e3)
        t0 = t1
    # min over batches rejects transient load on shared CI hosts; the
    # true cost is ~1.4 ms/img (PERF.md), bound leaves ~6x headroom
    assert best < 9.0, "decode cost regressed: %.2f ms/img" % best


def test_rec2idx_tool(tmp_path):
    """tools/rec2idx.py rebuilds a lost .idx from the .rec stream
    (reference tools/rec2idx.py IndexCreator)."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from rec2idx import create_index

    rec_p = str(tmp_path / "t.rec")
    w = MXIndexedRecordIO(str(tmp_path / "orig.idx"), rec_p, "w")
    for i in range(7):
        w.write_idx(i, b"payload-%d" % i)
    w.close()
    idx_p = str(tmp_path / "rebuilt.idx")
    assert create_index(rec_p, idx_p) == 7
    from incubator_mxnet_tpu.recordio import MXIndexedRecordIO as IR
    r = IR(idx_p, rec_p, "r")
    assert r.read_idx(4) == b"payload-4"
    # rebuilt index matches the writer's own
    orig = open(str(tmp_path / "orig.idx")).read().split()
    new = open(idx_p).read().split()
    assert orig == new


def test_image_det_record_iter(tmp_path):
    """ImageDetRecordIter (iter_image_det_recordio.cc): variable-length
    det labels padded with -1 to label_pad_width; geometric augment is
    rejected (boxes would be invalidated)."""
    prefix = str(tmp_path / "det")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    # det label: [header_width=2, object_width=5, (id,x1,y1,x2,y2)*n]
    labels = [
        np.array([2, 5, 0, .1, .1, .5, .5], np.float32),
        np.array([2, 5, 1, .2, .2, .6, .6, 0, .0, .0, .3, .3], np.float32),
    ]
    for i, lab in enumerate(labels):
        img = rng.randint(0, 255, (24, 24, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, lab, i, 0), img,
                                  img_fmt=".png"))
    rec.close()
    it = mio.ImageDetRecordIter(path_imgrec=prefix + ".rec",
                                path_imgidx=prefix + ".idx",
                                data_shape=(3, 24, 24), batch_size=2,
                                label_pad_width=12, shuffle=False)
    b = next(it)
    lab = b.label[0].asnumpy()
    assert lab.shape == (2, 12)
    np.testing.assert_allclose(lab[0][:7], labels[0])
    assert (lab[0][7:] == -1).all()          # -1 padding marks no-object
    np.testing.assert_allclose(lab[1], labels[1])
    assert b.data[0].shape == (2, 3, 24, 24)
    it.close()
    with pytest.raises(ValueError):
        mio.ImageDetRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 24, 24), batch_size=2,
                               rand_mirror=True)
    # label_pad_width unset: auto-estimated from the data (reference
    # iter_image_det_recordio.cc:337) — max width over the records
    it2 = mio.ImageDetRecordIter(path_imgrec=prefix + ".rec",
                                 path_imgidx=prefix + ".idx",
                                 data_shape=(3, 24, 24), batch_size=2,
                                 shuffle=False)
    assert next(it2).label[0].shape == (2, 12)
    it2.close()
    # a too-small explicit pad width fails LOUDLY (objects would drop)
    it3 = mio.ImageDetRecordIter(path_imgrec=prefix + ".rec",
                                 path_imgidx=prefix + ".idx",
                                 data_shape=(3, 24, 24), batch_size=2,
                                 label_pad_width=7, shuffle=False)
    with pytest.raises(Exception, match="label_pad_width"):
        next(it3)
    it3.close()
