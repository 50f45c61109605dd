#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput.

Reference baseline (BASELINE.md): MXNet-CUDA on V100, batch 128 fp32 —
363.69 img/s (docs perf.md:254).  This runs the same workload (ResNet-50,
224x224, SGD+momentum) as ONE fused XLA program per step (fwd+bwd+update,
bf16 compute / f32 state) on the local TPU chip, at batch 256 unless
``--batch`` says otherwise.  The JSON line reports the batch used, the
device it ran on and bf16 MFU against that device kind's published peak,
so the comparison basis is explicit.

This program measures the chip and nothing else: no accelerator, an
unknown device kind, a failed compile or a failed leg ends the run
non-zero with the error.  ``chip_smoke.py`` builds its train phase
through :func:`build_train_step` below, so the step this file times with
no flags and the step the smoke proves on the chip are one definition.

  * persistent XLA compilation cache (``incubator_mxnet_tpu._backend``:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``);
  * shape-only deferred init (HybridBlock.shape_init) — no eager pass;
  * warmup=1, then timed chunks; the JSON result line is printed after the
    FIRST chunk and refined after each later chunk;
  * per-phase wall times (import/build/init/trace/compile/step) on stderr.

Prints JSON lines of the form
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N}
(the last line printed is the most refined measurement).
"""
import argparse
import json
import os
import sys
import time

BASELINE_IMG_S = 363.69  # V100 fp32 batch-128 training (perf.md:254)
# The default train step — ONE definition: run_train defaults, argparse
# help, the main() fallbacks and chip_smoke.py's train phase all read
# these.  No passes: the composition a chip has run.
DEFAULT_PASSES = ""
DEFAULT_ZERO = 1  # ZeRO-1 on dp meshes (a no-op without --mesh-dp)
# ResNet-50 at 224x224: ~4.09 GFLOPs forward per image; training step
# (fwd + bwd) ~= 3x forward.
TRAIN_FLOPS_PER_IMG = 3 * 4.09e9
# Published bf16 peak per chip, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).  A
# kind that is not listed is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
REPO = os.path.dirname(os.path.abspath(__file__))
T0 = time.time()


def log(msg):
    print("[bench %7.1fs] %s" % (time.time() - T0, msg), file=sys.stderr,
          flush=True)


def setup_jax():
    import jax

    from incubator_mxnet_tpu import _backend

    _backend.use_compile_cache()
    return jax


def device_stamp():
    """What every record says about where it ran."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def peak_bf16_flops(device_kind):
    if device_kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            "no published bf16 peak for device kind %r — bench.py lists "
            "%s; add the kind with its source before reporting MFU on it"
            % (device_kind, sorted(PEAK_BF16_FLOPS)))
    return PEAK_BF16_FLOPS[device_kind]


def require_tpu(n_devices=1):
    """A missing chip ends the run: there is no CPU continuation."""
    jax = setup_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_devices:
        raise SystemExit(
            "bench.py measures the TPU: it needs %d tpu device(s) and jax "
            "found %r" % (n_devices, devices))
    log("devices: %s" % (devices,))
    return devices


def emit(metric, value, unit, baseline, extra=None):
    rec = {"metric": metric, "value": round(value, 2), "unit": unit,
           "vs_baseline": round(value / baseline, 3) if baseline else 0.0}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def _synth_recordio(image_size, n=512, img_fmt=".jpg"):
    """Synthesize (once, cached on disk) a recordio shard for the
    --data recordio mode; returns the file prefix.  img_fmt '.npy' writes
    raw payloads (no JPEG decode cost — isolates the IO path from the
    host's decode throughput, which matters on few-core hosts)."""
    import numpy as np

    from incubator_mxnet_tpu.recordio import (IRHeader, MXIndexedRecordIO,
                                              pack_img)

    tag = "" if img_fmt == ".jpg" else img_fmt.replace(".", "_")
    prefix = os.path.join(REPO, ".bench_data", "synth%d%s" % (image_size,
                                                              tag))
    if os.path.exists(prefix + ".idx"):
        return prefix
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    rng = np.random.RandomState(0)
    # write under a tmp name and publish atomically so a mid-synthesis kill
    # can't leave a truncated shard that later runs mistake for complete
    tmp = prefix + ".tmp"
    rec = MXIndexedRecordIO(tmp + ".idx", tmp + ".rec", "w")
    for i in range(n):
        img = rng.randint(0, 255, (image_size, image_size, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i % 1000), i, 0), img,
                                  quality=90, img_fmt=img_fmt))
    rec.close()
    os.replace(tmp + ".rec", prefix + ".rec")
    os.replace(tmp + ".idx", prefix + ".idx")
    log("synthesized %d-record shard at %s" % (n, prefix))
    return prefix


def parse_passes(passes):
    """'a,b' / iterable of names -> tuple of names ('' / None -> ())."""
    if isinstance(passes, str):
        passes = passes.split(",")
    return tuple(s.strip() for s in (passes or ()) if s.strip())


def build_train_step(image_size=224, classes=1000, passes=DEFAULT_PASSES,
                     mesh=None, zero=DEFAULT_ZERO, multi_precision=True,
                     loss_scale="dynamic", compute_dtype="bfloat16",
                     learning_rate=0.1, cost="report", seed=0):
    """The benchmark's train step, built in ONE place: ResNet-50 v1 from a
    seed, SGD momentum 0.9 / wd 1e-4, bf16 compute with f32 master
    weights, dynamic loss scale.  ``run_train`` times it and
    ``chip_smoke.py`` proves it on the chip; with no arguments both get
    the same program.  ``passes`` is a comma list / tuple of graftpass
    names or a canonical PassSchedule dict.  Returns ``(net, step)``."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(seed)
    net = vision.resnet50_v1(classes=classes)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))  # no eager pass
    if not isinstance(passes, dict):
        passes = parse_passes(passes)
    # cost="report": the graftcost roofline prediction rides the same
    # pre-compile trace and lands in the JSON line next to the measured
    # number, so every record logs predicted-vs-measured drift
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           optimizer="sgd", learning_rate=learning_rate,
                           momentum=0.9, wd=1e-4, mesh=mesh,
                           zero=zero if mesh is not None else 0,
                           multi_precision=multi_precision,
                           loss_scale=loss_scale,
                           compute_dtype=compute_dtype, cost=cost,
                           passes=passes)
    return net, step


def dp_mesh(n):
    """A dp=n mesh over the first n devices; fewer than n is an error,
    never a smaller mesh."""
    import jax

    from incubator_mxnet_tpu.parallel import make_mesh

    if len(jax.devices()) < n:
        raise RuntimeError("a dp=%d mesh needs %d devices, jax has %d: %r"
                           % (n, n, len(jax.devices()), jax.devices()))
    return make_mesh({"dp": n}, devices=jax.devices()[:n])


def run_train(batch_size=256, image_size=224, chunks=8, chunk_iters=5,
              compute_dtype="bfloat16", data="synthetic",
              record_format=".jpg", passes=DEFAULT_PASSES, mesh_dp=0,
              zero=DEFAULT_ZERO, multi_precision=True, loss_scale="dynamic",
              schedule_config=None):
    jax = setup_jax()
    import numpy as np

    from incubator_mxnet_tpu import nd

    pass_names = parse_passes(passes)
    pass_arg = pass_names
    sched_extra = {}
    if schedule_config:
        # graftsched winner (tools/autotune.py --target train-schedule
        # --winner-out): knobs.schedule is the canonical PassSchedule
        # dict make_train_step(passes=) accepts directly; the stamped
        # schedule_hash is the cross-check that THIS step resolved the
        # SAME per-site decision vector the tuner ranked
        with open(schedule_config) as f:
            win = json.load(f)
        win_knobs = win.get("knobs", win)
        sched = win_knobs.get("schedule")
        if not isinstance(sched, dict) or "passes" not in sched:
            raise ValueError("--schedule-config %s has no knobs.schedule "
                             "canonical dict (run tools/autotune.py "
                             "--target train-schedule --winner-out)"
                             % schedule_config)
        pass_arg = sched
        pass_names = tuple(e["name"] for e in sched["passes"])
        sched_extra = {"schedule_source": os.path.basename(schedule_config),
                       "schedule_hash_winner":
                       win_knobs.get("schedule_hash")}
        log("schedule-config %s: %d-pass per-site schedule, winner hash "
            "%s (tuner predicted %s s/sample on %s)"
            % (schedule_config, len(pass_names),
               win_knobs.get("schedule_hash"),
               win.get("measured_s_per_sample"),
               win.get("backend", "?")))

    stamp = device_stamp()
    peak = peak_bf16_flops(stamp["device_kind"])
    mesh = dp_mesh(mesh_dp) if mesh_dp and mesh_dp > 1 else None
    if mesh is not None:
        log("dp=%d mesh (zero=%s)" % (mesh_dp, zero))
    t = time.time()
    net, step = build_train_step(
        image_size=image_size, passes=pass_arg, mesh=mesh,
        zero=zero, multi_precision=multi_precision,
        loss_scale=loss_scale, compute_dtype=compute_dtype)
    log("build+param-init+shape_init %.1fs" % (time.time() - t))
    if sched_extra:
        want = sched_extra.get("schedule_hash_winner")
        got = step.schedule_hash
        if want and want != got:
            # loud: a hash drift means the measured number belongs to a
            # DIFFERENT schedule than the tuning log ranked
            log("WARNING: schedule hash drift — winner config says %s, "
                "the built step resolved %s" % (want, got))
            sched_extra["schedule_hash_drift"] = True
        else:
            log("schedule %s stamped on the step (matches the winner "
                "config)" % got)

    if data == "recordio":
        # recordio feeds raw uint8 batches (ImageRecordUInt8Iter) — compile
        # for THAT signature or the timed chunks pay a hidden retrace
        x = nd.array(np.zeros((batch_size, 3, image_size, image_size),
                              np.uint8))
    else:
        x = nd.random.uniform(shape=(batch_size, 3, image_size, image_size))
    y = nd.array(np.random.randint(0, 1000, batch_size).astype(np.float32))

    log("AOT trace+lower+compile at batch %d..." % batch_size)
    times = step.aot_compile(x, y)
    log("trace+lower %.1fs, XLA compile %.1fs" %
        (times["trace"], times["compile"]))

    t = time.time()
    loss = step(x, y)
    loss.wait_to_read()
    log("warmup step %.2fs (loss=%.3f)" % (time.time() - t,
                                           float(loss.asscalar())))

    # graftcost prediction (computed at trace time by cost="report"):
    # counts of bytes and FLOPs, logged beside the measurement
    rep = step.cost_report
    rf = rep.roofline()
    pred = {"pred_bytes_per_img": round(rep.hbm_bytes / batch_size),
            "pred_hbm_gib_step": round(rep.hbm_bytes / 2**30, 2),
            "pred_ms_per_step": round(1e3 * rf["step_s"], 2),
            "pred_img_per_sec": round(batch_size / rf["step_s"], 1)
            if rf["step_s"] else 0.0,
            "pred_peak_mb": round(rep.peak_bytes / 1e6, 1),
            "pred_multipass_gb": round(rep.multipass_extra_bytes / 1e9, 2)}
    log("graftcost: %.1f GiB/step HBM -> >= %.1f ms/step "
        "(%.0f img/s roofline), peak %.0f MB"
        % (rep.hbm_bytes / 2**30, 1e3 * rf["step_s"],
           pred["pred_img_per_sec"], rep.peak_bytes / 1e6))

    # UNFUSED reference prediction for a composed step: the lever-
    # attribution delta (byte count with and without the passes).  One
    # abstract trace, no compile (~seconds).
    if pass_names:
        t = time.time()
        # same mesh/zero knobs as the fused step: the delta must
        # attribute the byte diet, not dp-sharding differences.
        # passes=() explicit: MXTPU_PASSES must not leak into the
        # unfused baseline the delta is judged by
        _, ref_step = build_train_step(
            image_size=image_size, passes=(), mesh=mesh,
            zero=zero, multi_precision=multi_precision,
            loss_scale=loss_scale, compute_dtype=compute_dtype, cost="off")
        xs = jax.ShapeDtypeStruct(
            (batch_size, 3, image_size, image_size), np.float32)
        ys = jax.ShapeDtypeStruct((batch_size,), np.float32)
        ref_rep = ref_step.analyze_cost(xs, ys)
        pred["pred_bytes_per_img_unfused"] = round(
            ref_rep.hbm_bytes / batch_size)
        pred["pred_multipass_gb_unfused"] = round(
            ref_rep.multipass_extra_bytes / 1e9, 2)
        pred["pred_bytes_delta_pct"] = round(
            100.0 * (1.0 - pred["pred_bytes_per_img"]
                     / pred["pred_bytes_per_img_unfused"]), 1)
        log("graftcost unfused reference: %d bytes/img vs fused %s "
            "(delta %s%%, multipass %.2f -> %.2f GB) [%.1fs]"
            % (pred["pred_bytes_per_img_unfused"],
               pred["pred_bytes_per_img"], pred["pred_bytes_delta_pct"],
               pred["pred_multipass_gb_unfused"],
               pred["pred_multipass_gb"], time.time() - t))

    batch_src = None
    if data == "recordio":
        # uint8 iterator: 1/4 the host->device bytes; raw-bytes contract —
        # the step promotes to the compute dtype (a real consumer would
        # also apply its mean/std there)
        from incubator_mxnet_tpu.io import ImageRecordUInt8Iter

        prefix = _synth_recordio(image_size, img_fmt=record_format)
        rit = ImageRecordUInt8Iter(path_imgrec=prefix + ".rec",
                                   path_imgidx=prefix + ".idx",
                                   data_shape=(3, image_size, image_size),
                                   batch_size=batch_size, shuffle=True,
                                   rand_mirror=True, preprocess_threads=8,
                                   prefetch_buffer=8)

        def batch_src():
            try:
                b = next(rit)
            except StopIteration:
                rit.reset()
                b = next(rit)
            return b.data[0], b.label[0]

    metric = ("resnet50_train_img_per_sec" if data == "synthetic"
              else "resnet50_train_recordio_img_per_sec")
    best = 0.0
    for c in range(chunks):
        t = time.time()
        for _ in range(chunk_iters):
            if batch_src is not None:
                x, y = batch_src()
            loss = step(x, y)
        loss.wait_to_read()
        dt = time.time() - t
        img_s = chunk_iters * batch_size / dt
        best = max(best, img_s)
        log("chunk %d: %d iters in %.3fs -> %.1f img/s (step %.1f ms)"
            % (c, chunk_iters, dt, img_s, 1e3 * dt / chunk_iters))
        extra = {"batch": batch_size, "dtype": compute_dtype, "data": data,
                 "bn": "batch",
                 "passes": list(pass_names),
                 "schedule_hash": step.schedule_hash,
                 "multi_precision": bool(multi_precision),
                 "loss_scale": str(loss_scale),
                 "mesh": ("dp%d" % mesh_dp) if mesh is not None else "none",
                 "zero": int(zero) if mesh is not None else 0,
                 "step_ms": round(1e3 / (best / batch_size), 2),
                 "mfu_bf16": round(best * TRAIN_FLOPS_PER_IMG / peak
                                   / (mesh_dp if mesh is not None else 1),
                                   4),
                 "trace_s": round(times["trace"], 1),
                 "compile_s": round(times["compile"], 1),
                 "chunks_done": c + 1}
        extra.update(stamp)
        extra.update(pred)
        extra.update(sched_extra)
        emit(metric, best, "img/s", BASELINE_IMG_S, extra)
    return best


BASELINE_INFER_IMG_S = 2355.04  # V100 fp16 batch-128 inference (perf.md:210)


def run_serve(batch_bucket=64, image_size=224, qps=400.0, n_requests=200,
              max_delay_ms=10.0):
    """Serving leg: ResNet-50 through serve/ (AOT bucketed engine +
    continuous batcher) under open-loop Poisson traffic — the
    `serve_qps`/`serve_p99_ms` metrics logged beside the training
    throughput (docs/SERVING.md)."""
    jax = setup_jax()
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.serve import (ContinuousBatcher, ServeEngine,
                                           poisson_loadtest)

    stamp = device_stamp()
    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))  # no eager pass
    buckets = tuple(sorted({max(1, batch_bucket // 4), batch_bucket}))
    eng = ServeEngine(net, buckets=buckets, lint="error", cost="report")
    t = eng.warmup(np.zeros((3, image_size, image_size), np.float32))
    log("serve warmup: %d buckets, trace %.1fs + compile %.1fs"
        % (len(buckets), t["trace"], t["compile"]))
    pool = np.random.RandomState(0).rand(
        8, 3, image_size, image_size).astype(np.float32)
    batcher = ContinuousBatcher(eng, max_delay=max_delay_ms / 1e3)
    try:
        rep = poisson_loadtest(batcher, lambda i, rng: pool[i % 8],
                               qps=qps, n_requests=n_requests, seed=0)
    finally:
        batcher.close()
    log(rep.format())
    extra = {"p50_ms": round(rep.p50_ms, 2), "p95_ms": round(rep.p95_ms, 2),
             "p99_ms": round(rep.p99_ms, 2), "qps_offered": qps,
             "ok": rep.ok, "errors": rep.errors, "shed": rep.shed,
             "recompiles": rep.recompiles, "buckets": list(buckets),
             "schedule_hash": eng.schedule_hash,
             "occupancy": {str(k): v for k, v in
                           sorted(rep.occupancy.items())},
             "warmup_compile_s": round(t["compile"], 1)}
    extra.update(stamp)
    emit("serve_qps", rep.qps_sustained, "req/s", 0.0, extra)
    emit("serve_p99_ms", rep.p99_ms, "ms", 0.0,
         dict(stamp, p50_ms=round(rep.p50_ms, 2),
              recompiles=rep.recompiles))
    return rep


def run_infer_int8(batch_size=128, image_size=224, iters=20):
    """INT8 ResNet-50 inference through the round-4 int8 wire
    (fold_batch_norm + requantize chaining + quantized residual adds,
    docs/PERF.md) vs the bf16 forward — reports both img/s and the ratio.
    """
    jax = setup_jax()
    import tempfile

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.contrib.quantization import (fold_batch_norm,
                                                          quantize_model)
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    stamp = device_stamp()
    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "r50")
        net.export(prefix)
        sym, args, aux = mx.model.load_checkpoint(prefix, 0)
    fsym, fargs, faux = fold_batch_norm(sym, args, aux)
    qsym, qargs, qaux = quantize_model(fsym, fargs, faux, calib_mode="none")
    xnp = np.random.RandomState(0).uniform(
        size=(batch_size, 3, image_size, image_size)).astype(np.float32)

    def bind(s, a, au):
        binds = dict(a)
        binds["data"] = nd.array(xnp)
        # mx.tpu(): main() has required a tpu device, so this is the chip
        # (mx.cpu() would bind on the HOST's cpu, context.py)
        return s.bind(mx.tpu(), args=binds, aux_states=au), binds["data"]

    results = {}
    for tag, (s_, a_, au_) in (("bf16", (fsym, fargs, faux)),
                               ("int8", (qsym, qargs, qaux))):
        if tag == "bf16":
            a_ = {k: v.astype("bfloat16") if str(v.dtype).startswith("f")
                  else v for k, v in a_.items()}
        exe, xin = bind(s_, a_, au_)
        if tag == "bf16":
            xin._data = xin._data.astype("bfloat16")
        t = time.time()
        (out,) = exe.forward(is_train=False)
        out.wait_to_read()
        log("%s first forward (compile) %.1fs" % (tag, time.time() - t))
        best = 0.0
        for _ in range(3):
            t = time.time()
            for _ in range(iters):
                (out,) = exe.forward(is_train=False)
            out.wait_to_read()
            best = max(best, iters * batch_size / (time.time() - t))
        results[tag] = best
        log("%s: %.0f img/s" % (tag, best))
    emit("resnet50_int8_infer_img_per_sec", results["int8"], "img/s",
         BASELINE_INFER_IMG_S,
         dict(stamp, batch=batch_size,
              bf16_img_per_sec=round(results["bf16"], 1),
              int8_over_bf16=round(results["int8"] / results["bf16"], 3)))
    return results


def run_infer(batch_size=128, image_size=224, iters=30):
    """ResNet-50 inference throughput (perf.md:189-210 benchmark_score.py
    analog): hybridized forward as one XLA program, bf16."""
    jax = setup_jax()
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    stamp = device_stamp()
    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))
    net.cast("bfloat16")
    net.hybridize(static_alloc=True)

    x = nd.random.uniform(
        shape=(batch_size, 3, image_size, image_size)).astype("bfloat16")
    t = time.time()
    out = net(x)
    out.wait_to_read()
    log("first forward (trace+compile) %.1fs" % (time.time() - t))

    best = 0.0
    for chunk in range(4):
        t = time.time()
        for _ in range(iters):
            out = net(x)
        out.wait_to_read()
        dt = time.time() - t
        img_s = iters * batch_size / dt
        best = max(best, img_s)
        log("chunk %d: %.1f img/s (%.2f ms/batch)"
            % (chunk, img_s, 1e3 * dt / iters))
        emit("resnet50_infer_img_per_sec", best, "img/s",
             BASELINE_INFER_IMG_S,
             dict(stamp, batch=batch_size, dtype="bfloat16",
                  chunks_done=chunk + 1))
    return best


def run_attention(seq=2048, heads=8, head_dim=128, batch=4, iters=20):
    """Compiled (non-interpret) Pallas flash attention on the chip, checked
    against the reference attention and timed vs jax.nn.dot_product_attention.
    """
    jax = setup_jax()
    import jax.numpy as jnp
    import numpy as np

    import importlib

    # the package re-exports the flash_attention FUNCTION; fetch the module
    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    from incubator_mxnet_tpu.parallel.ring_attention import attention_reference

    stamp = device_stamp()
    rng = np.random.RandomState(0)
    shape = (batch, heads, seq, head_dim)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32)) * 0.1
               for _ in range(3))

    # default path (XLA fused attention since round 4 — docs/PERF.md);
    # the Pallas kernels stay measurable via use_pallas=True below
    flash = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    pallas = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, use_pallas=True))
    t = time.time()
    out = flash(q, k, v).block_until_ready()
    log("flash attention compile+run %.1fs" % (time.time() - t))

    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-3)
    log("flash == reference (rtol 2e-2)")

    # backward: compiled flash bwd kernels vs autodiff of the reference
    pallas(q, k, v).block_until_ready()
    t = time.time()
    for _ in range(iters):
        outp = pallas(q, k, v)
    outp.block_until_ready()
    dt_pallas = (time.time() - t) / iters
    log("pallas kernel fwd %.2f ms" % (1e3 * dt_pallas))
    flash_grad = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2)))
    t = time.time()
    dq, dk, dv = flash_grad(q, k, v)
    jax.block_until_ready((dq, dk, dv))
    log("flash bwd compile+run %.1fs" % (time.time() - t))
    ref_grad = jax.jit(jax.grad(
        lambda q, k, v: attention_reference(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2)))
    rdq, rdk, rdv = ref_grad(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), rtol=5e-2,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), rtol=5e-2,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), rtol=5e-2,
                               atol=5e-3)
    log("flash bwd == reference autodiff")
    t = time.time()
    for _ in range(iters):
        outs = flash_grad(q, k, v)
    jax.block_until_ready(outs)
    log("flash fwd+bwd %.2f ms" % (1e3 * (time.time() - t) / iters))

    t = time.time()
    for _ in range(iters):
        out = flash(q, k, v)
    out.block_until_ready()
    dt_flash = (time.time() - t) / iters

    xla_attn = jax.jit(
        lambda q, k, v: jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), is_causal=True).transpose(0, 2, 1, 3))
    xla_attn(q, k, v).block_until_ready()
    t = time.time()
    for _ in range(iters):
        out2 = xla_attn(q, k, v)
    out2.block_until_ready()
    dt_xla = (time.time() - t) / iters

    log("flash %.2f ms vs xla attention %.2f ms" % (1e3 * dt_flash,
                                                    1e3 * dt_xla))
    emit("flash_attention_ms", 1e3 * dt_flash, "ms", 1e3 * dt_xla,
         dict(stamp, seq=seq, heads=heads, head_dim=head_dim, batch=batch,
              xla_attention_ms=round(1e3 * dt_xla, 3),
              pallas_ms=round(1e3 * dt_pallas, 3), default_backend="xla"))

    # long-sequence crossover sweep (asked for in the round-4 review): the
    # Pallas kernel's reason to exist is O(L) memory at long L — find the
    # length where it beats the XLA kernel, or prove there is none
    def timeit(fn, *args, n=10):
        fn(*args)
        jax.block_until_ready(fn(*args))
        t0 = time.time()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (time.time() - t0) / n

    for long_seq in (4096, 8192, 16384):
        b = 1
        shape = (b, heads, long_seq, head_dim)
        q, k, v = (jnp.asarray(
            rng.normal(size=shape).astype(np.float32)) * 0.1
            for _ in range(3))
        row = dict(stamp, seq=long_seq, heads=heads, head_dim=head_dim,
                   batch=b)
        # mini block-size tune: bigger k-blocks amortize grid
        # overhead at long L (v5e MXU likes 256x512 tiles)
        best_blocks, p_f = None, float("inf")
        for bq, bk in ((128, 128), (256, 512), (512, 512)):
            pk = jax.jit(lambda q, k, v, bq=bq, bk=bk:
                         fa.flash_attention(q, k, v, causal=True,
                                            use_pallas=True,
                                            block_q=bq, block_k=bk))
            ms = timeit(pk, q, k, v)
            if ms < p_f:
                best_blocks, p_f = (bq, bk), ms
        row["pallas_blocks"] = list(best_blocks)
        x_f = timeit(flash, q, k, v)
        bq, bk = best_blocks
        pallas_grad = jax.jit(jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, use_pallas=True,
                block_q=bq, block_k=bk).sum(),
            argnums=(0, 1, 2)))
        p_fb = timeit(pallas_grad, q, k, v, n=5)
        x_fb = timeit(flash_grad, q, k, v, n=5)
        row.update({"pallas_fwd_ms": round(p_f, 2),
                    "xla_fwd_ms": round(x_f, 2),
                    "pallas_fwd_bwd_ms": round(p_fb, 2),
                    "xla_fwd_bwd_ms": round(x_fb, 2),
                    "pallas_wins_fwd": bool(p_f < x_f),
                    "pallas_wins_fwd_bwd": bool(p_fb < x_fb)})
        log("seq %d: pallas fwd %.2f / xla fwd %.2f ms; "
            "fwd+bwd %.2f / %.2f ms"
            % (long_seq, p_f, x_f, p_fb, x_fb))
        emit("attention_crossover_seq%d" % long_seq,
             row["pallas_fwd_bwd_ms"], "ms", row["xla_fwd_bwd_ms"], row)
    return dt_flash


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="train",
                    choices=["train", "infer", "infer-int8", "attention",
                             "serve"])
    ap.add_argument("--no-serve", action="store_true",
                    help="skip the serving leg after the training run")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "recordio"])
    ap.add_argument("--passes", default=DEFAULT_PASSES,
                    help="comma-separated graftpass names for the train "
                         "step (default %r; '' = none)" % DEFAULT_PASSES)
    ap.add_argument("--mesh-dp", type=int, default=0,
                    help="build the step over a dp=N mesh (composes with "
                         "--zero); fewer than N tpu devices is an error")
    ap.add_argument("--zero", type=int, default=DEFAULT_ZERO,
                    choices=[0, 1],
                    help="ZeRO-1 state sharding on the dp mesh "
                         "(no effect without --mesh-dp)")
    ap.add_argument("--no-multi-precision", action="store_true",
                    help="disable f32 master weights")
    ap.add_argument("--loss-scale", default="dynamic",
                    help="'dynamic' (default), a float, or 'off'")
    ap.add_argument("--schedule-config", default=None,
                    help="path to an autotune winner JSON (tools/"
                         "autotune.py --target train-schedule "
                         "--winner-out): the step is built with the "
                         "winner's per-site PassSchedule instead of "
                         "--passes, and its schedule_hash is stamped on "
                         "every metric record")
    ap.add_argument("--record-format", default=".jpg",
                    choices=[".jpg", ".npy"],
                    help=".npy writes raw payloads — no JPEG decode cost "
                         "(isolates IO from single-core decode limits)")
    args = ap.parse_args()

    loss_scale = args.loss_scale
    if loss_scale not in ("dynamic", "off"):
        try:
            loss_scale = float(loss_scale)
        except ValueError:
            ap.error("--loss-scale must be 'dynamic', 'off' or a float "
                     "(got %r)" % loss_scale)
    elif loss_scale == "off":
        loss_scale = None

    # any failure from here on — no chip, a refused compile, a failed
    # leg — propagates: the run ends non-zero with the error
    require_tpu(max(1, args.mesh_dp))

    if args.mode == "attention":
        run_attention()
    elif args.mode == "infer":
        run_infer(batch_size=args.batch or 128, image_size=args.image_size)
    elif args.mode == "infer-int8":
        run_infer_int8(batch_size=args.batch or 128,
                       image_size=args.image_size)
    elif args.mode == "serve":
        run_serve(batch_bucket=args.batch or 64,
                  image_size=args.image_size)
    else:
        run_train(batch_size=args.batch or 256, image_size=args.image_size,
                  chunks=args.chunks, data=args.data,
                  record_format=args.record_format, passes=args.passes,
                  mesh_dp=args.mesh_dp, zero=args.zero,
                  multi_precision=not args.no_multi_precision,
                  loss_scale=loss_scale,
                  schedule_config=args.schedule_config)
        if not args.no_serve:
            run_serve(image_size=args.image_size)


if __name__ == "__main__":
    main()
