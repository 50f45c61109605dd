#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip, the entry points a user calls: ``vision.resnet50_v1``
-> ``make_train_step`` -> ``aot_compile`` -> steps, then ``ServeEngine`` +
``ContinuousBatcher`` under open-loop traffic; between them the expert op
of the decoder families with NaN where its rows end, and the KDA recurrence's
kernels against the recurrence token by token and, at the model's size,
against its chunked ``jax.numpy`` form.  Weights and data come
from ``--seed``.  No number printed here is a result: times are information for
whoever looks next, the checks are what the run is for.

    python chip_smoke.py              # one chip: device, train, experts, kda,
                                      # serve
    python chip_smoke.py --multichip  # four chips: dp=4 ZeRO-1 step vs one chip

Contract with the driver: the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and
the exit code 0 when every phase passed.  Anything else — no accelerator, a
refused compile, a failed check — exits non-zero and prints no such line.
There is no CPU continuation, no retry and no watchdog.

Each phase is a plain function of its sizes, so ``tests/test_chip_smoke.py``
runs the same code at tiny sizes on the CPU; ``main()`` fixes the real sizes
and is the only place that looks at the device.
"""
import argparse
import gc
import importlib.metadata
import json
import statistics
import sys
import time

T0 = time.time()

#: what jax reports for every XLA program it builds or loads from its cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: first-step loss of the dp=4 step against the one-chip step.  The step is
#: one GSPMD program over the GLOBAL batch, so BatchNorm reduces its
#: statistics across the four shards (an all-reduce) and the two steps
#: compute the same function — in float32 they agree to 1e-4 at any size.
#: Under bf16 compute what differs is rounding: the loss itself is a bf16
#: number (one ulp at 6.9 is 0.45 %), and a 64-image shard and a 256-image
#: batch get different conv tilings and reduction orders.  At toy sizes
#: (BatchNorm over 8 values) that noise reaches 7 %; at the real size a
#: few ulps:
MULTICHIP_LOSS_RTOL = 3e-2


class SmokeFailure(AssertionError):
    """A check of a phase did not hold."""


def log(msg):
    print("[smoke %6.1fs] %s" % (time.time() - T0, msg), flush=True)


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


class _CompileCounter:
    """Counts the XLA programs built (or loaded from the persistent cache)
    while it is armed — jax's own monitoring event, so a silent retrace
    behind an AOT executable is seen too."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if self.armed and name == _COMPILE_EVENT:
            self.count += 1


_COUNTER = None


def _compile_counter():
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def device(platform, count):
    """``jax.devices()`` must be at least ``count`` devices of ``platform``;
    anything else ends the run.  Returns the contract's device record."""
    import jax
    import jaxlib

    devs = jax.devices()
    check(devs[0].platform == platform,
          "jax found platform %r, this run needs %r: %r"
          % (devs[0].platform, platform, devs))
    check(len(devs) >= count,
          "this run needs %d %s device(s), jax found %d: %r"
          % (count, platform, len(devs), devs))
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    from incubator_mxnet_tpu import _backend

    cache = _backend.use_compile_cache()
    log("device: %s x%d (%s)  jax %s  jaxlib %s  libtpu %s  python %s"
        % (devs[0].device_kind, len(devs), platform, jax.__version__,
           jaxlib.__version__, libtpu, sys.version.split()[0]))
    log("device: compile cache at %s" % cache)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------

def _batch(batch, image_size, classes, seed):
    import numpy as np

    from incubator_mxnet_tpu import nd

    rng = np.random.RandomState(seed)
    x = rng.uniform(size=(batch, 3, image_size, image_size))
    y = rng.randint(0, classes, batch)
    return nd.array(x.astype(np.float32)), nd.array(y.astype(np.float32))


def _state_arrays(step):
    import jax

    params = [p.data()._data for p in
              step.net.collect_params().values()]
    return params, jax.tree.leaves(step.opt_state)


def _check_placed(arrays, platform, what):
    """Every array lives on ``platform`` devices and nowhere else — the
    check a silent ``ctx=mx.tpu()``-on-a-cpu-host run cannot get through."""
    off = [a for a in arrays
           if {d.platform for d in a.devices()} != {platform}]
    check(not off, "%s: %d of %d arrays are not on a %s device (first: %r)"
          % (what, len(off), len(arrays), platform,
             off[0].devices() if off else None))


def _run_steps(step, x, y, n):
    """n steps, each timed to the loss being ready on the host."""
    losses, ms = [], []
    for _ in range(n):
        t = time.time()
        loss = step(x, y)
        loss.wait_to_read()
        ms.append(1e3 * (time.time() - t))
        losses.append(float(loss.asscalar()))
    return losses, ms


def _check_losses(losses, what):
    import math

    check(all(math.isfinite(v) for v in losses),
          "%s: a loss is not finite: %r" % (what, losses))
    check(min(losses[-3:]) < losses[0],
          "%s: the loss did not fall: first %r, last three %r"
          % (what, losses[0], losses[-3:]))


def train(batch, image_size, steps, platform, passes=None, classes=1000,
          seed=0, **step_kwargs):
    """The train step ``bench.py`` runs with no flags (``passes`` None = its
    default), AOT-compiled, 1 warm-up + ``steps`` steps on one fixed batch.
    Returns what it measured.  ``step_kwargs`` reach
    ``bench.build_train_step`` (a toy-sized run needs a smaller
    ``learning_rate`` than the recipe's to see its loss fall)."""
    import jax

    import bench

    passes = bench.DEFAULT_PASSES if passes is None else passes
    log("train: resnet50_v1 classes=%d batch=%d %dpx bf16, passes=%r"
        % (classes, batch, image_size, passes))
    _, step = bench.build_train_step(image_size=image_size, classes=classes,
                                     passes=passes, seed=seed, **step_kwargs)
    x, y = _batch(batch, image_size, classes, seed)
    times = step.aot_compile(x, y)
    log("train: trace %.1fs, compile %.1fs" % (times["trace"],
                                               times["compile"]))
    mem = step.compiled.memory_analysis()
    log("train: the compiler counts %.2f GB of arguments + %.2f GB of "
        "temporaries"
        % (mem.argument_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9))

    counter = _compile_counter()
    first, warm_ms = _run_steps(step, x, y, 1)
    counter.count, counter.armed = 0, True
    try:
        losses, ms = _run_steps(step, x, y, steps)
    finally:
        counter.armed = False
    losses = first + losses
    log("train: warm-up step %.1f ms; losses %s"
        % (warm_ms[0], " ".join("%.4f" % v for v in losses)))

    params, state = _state_arrays(step)
    _check_placed(params + state, platform,
                  "train: parameters and optimizer state")
    _check_losses(losses, "train")
    check(counter.count == 0,
          "train: %d XLA program(s) built after the warm-up" % counter.count)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    med = statistics.median(ms)
    # (the allocator's peak leaves out a compiled program's temporaries:
    # how near a step is to HBM is the compiler's count above)
    log("train: median step %.2f ms (%.1f img/s) over %d steps on %s; "
        "%d params + %d state arrays all on %s; allocator "
        "peak_bytes_in_use %s"
        % (med, 1e3 * batch / med, steps, jax.devices()[0].device_kind,
           len(params), len(state), platform,
           "not reported" if peak is None else "%.2f GB" % (peak / 1e9)))
    return {"losses": losses, "step_ms": med, "trace_s": times["trace"],
            "compile_s": times["compile"], "peak_bytes": peak}


# ---------------------------------------------------------------------------
# phase 3: the expert op past its held rows
# ---------------------------------------------------------------------------

def experts(rows, hidden, width, held, act, platform, seed=0):
    """``parallel.moe.moe_experts`` and its backward in bf16 on a buffer of
    ``rows`` rows of which an eighth (and 37, so that ``n = sum(sizes)``
    lies inside a block) are in a group.  The op writes nothing past the
    block of ``n`` (its gate, the gate's transpose and the sum of the two
    row cotangents stop there) and says that what it returns past ``n`` is
    not defined; what it must not do is let anything past ``n`` reach what
    IS read: the grouped products between its passes are the compiler's,
    the weight gradients among them contract over the rows, and a product
    that masked by multiplying would carry a NaN across.  So: one program,
    called on sound arrays and again with ``rows`` and the cotangent NaN
    from row ``n`` on; ``ys[:n]``, ``d rows[:n]`` and the three weight
    gradients are finite and bit for bit the same."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu.parallel import moe

    n = rows // 8 + 37
    sizes = np.random.RandomState(seed % 2 ** 32).multinomial(
        n, [1.0 / held] * held).astype(np.int32)
    log("experts: %d x %d rows, %d experts of width %d (%s), n = %d: %s"
        % (rows, hidden, held, width, act, n, sizes.tolist()))
    keys = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 5)
    x, g = (jax.random.normal(k, (rows, hidden), jnp.bfloat16)
            for k in keys[:2])
    w1, w3 = (jax.random.normal(k, (held, hidden, width), jnp.bfloat16)
              * hidden ** -0.5 for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (held, width, hidden),
                           jnp.bfloat16) * width ** -0.5
    live = (jnp.arange(rows) < n)[:, None]

    @jax.jit
    def both(x, g, w1, w3, w2):
        ys, pull = jax.vjp(
            lambda *o: moe.moe_experts(*o, jnp.asarray(sizes), act=act),
            x, w1, w3, w2)
        d_rows, d_w1, d_w3, d_w2 = pull(g)
        return ys[:n], d_rows[:n], d_w1, d_w3, d_w2

    names = ("ys[:n]", "d rows[:n]", "d w1", "d w3", "d w2")
    t = time.time()
    sound = both(x, g, w1, w3, w2)
    _check_placed(list(sound), platform, "experts: results")
    sound = [np.asarray(a.astype(jnp.float32)) for a in sound]
    poisoned = [np.asarray(a.astype(jnp.float32)) for a in both(
        *(jnp.where(live, a, jnp.nan) for a in (x, g)), w1, w3, w2)]
    log("experts: both calls %.1fs; largest |value| %s"
        % (time.time() - t, " ".join("%s %.3g" % (k, abs(a).max())
                                     for k, a in zip(names, sound))))
    for name, a, b in zip(names, sound, poisoned):
        check(np.isfinite(a).all() and a.any(),
              "experts: %s of the sound call is not finite, or is zero" % name)
        check(np.isfinite(b).all(),
              "experts: NaN past n reached %s: %d values not finite"
              % (name, (~np.isfinite(b)).sum()))
        check(np.array_equal(a, b),
              "experts: %s differs with NaN past n: %d values, by %.3g at "
              "most" % (name, (a != b).sum(), abs(a - b).max()))
    return n


def _recurrence(q, k, v, g, beta):
    """The KDA recurrence token by token (``lax.scan``), float32 at the
    highest precision: ``S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k
    v^T``, ``o_t = S_t^T q_t``, on the op's flat ``(1, S, H * D)`` arrays
    and ``beta`` ``(1, S, H)``.  It shares nothing with the kernels."""
    import functools

    import jax
    import jax.numpy as jnp

    mv = functools.partial(jnp.einsum, "hkv,hk->hv",
                           precision=jax.lax.Precision.HIGHEST)
    s, h = beta.shape[1:]
    q, k, v, g = (x[0].astype(jnp.float32).reshape(s, h, -1)
                  for x in (q, k, v, g))

    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - mv(S, kt))
        S = S + kt[..., None] * u[:, None, :]
        return S, mv(S, qt)

    S0 = jnp.zeros((h, q.shape[-1], v.shape[-1]), jnp.float32)
    o = jax.lax.scan(token, S0, (q, k, v, g, beta[0]))[1]
    return o.reshape(1, s, -1)


def kda(seq, heads, head_dim, platform, seed=0, witness=(1536, 2)):
    """The KDA recurrence's Pallas kernels (``parallel.delta_rule.kda``),
    forward and backward, on sequences as the model gives them (q, k, v in
    bf16, L2-normed q and k, g and beta in float32; log decays ``-A dt`` a
    channel with A uniform over 1-16 and dt log-uniform over 1e-3-1e-1, so
    that some channels forget within a token and some hold hundreds):

    * against the recurrence token by token (``_recurrence``) on
      ``witness`` = (tokens, heads) at the same head size: the output and
      the five gradients as near as rounding leaves them.  This is the
      check of the chunk mathematics and of the backward pass;
    * at ``seq`` tokens of ``heads`` heads, against the same chunk
      mathematics as plain XLA (``kda_chunked``): a check of the kernels'
      lowering at the model's size, where the token-by-token witness would
      take too long."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu.parallel import delta_rule

    def inputs(seq, heads, key):
        keys = jax.random.split(key, 8)
        shape = (1, seq, heads, head_dim)

        def unit(k):
            x = jax.random.normal(k, shape, jnp.float32)
            x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            return x.reshape(1, seq, -1).astype(jnp.bfloat16)

        q = (unit(keys[0]).astype(jnp.float32)
             / np.sqrt(head_dim)).astype(jnp.bfloat16)
        k = unit(keys[1])
        v = jax.random.normal(keys[2], (1, seq, heads * head_dim),
                              jnp.bfloat16)
        a = jax.random.uniform(keys[3], (heads, 1), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(keys[4], (heads, head_dim),
                                        jnp.float32, np.log(1e-3),
                                        np.log(1e-1)))
        g = -jnp.broadcast_to((a * dt).reshape(-1), v.shape) * jnp.exp(
            0.3 * jax.random.normal(keys[5], v.shape, jnp.float32))
        beta = jax.nn.sigmoid(jax.random.normal(keys[6], (1, seq, heads)))
        do = jax.random.normal(keys[7], v.shape, jnp.bfloat16)
        return (q, k, v, g, beta), do

    def both(f, do):
        def run(q, k, v, g, beta):
            o, pull = jax.vjp(f, q, k, v, g, beta)
            return (o,) + pull(do.astype(o.dtype))
        return jax.jit(run)

    def compare(what, got, want):
        names = ("o", "dq", "dk", "dv", "dg", "dbeta")
        got = [np.asarray(a.astype(jnp.float32)) for a in got]
        want = [np.asarray(a.astype(jnp.float32)) for a in want]
        for name, a, b in zip(names, got, want):
            scale = float(np.abs(b).max())
            err = float(np.abs(a - b).max())
            log("kda: %s largest |value| %.3g, kernels against %s %.3g"
                % (name, scale, what, err))
            check(np.isfinite(a).all() and a.any(),
                  "kda: %s of the kernels is not finite, or is zero" % name)
            # o, dq, dk and dv leave in bf16, where two nearly equal float32
            # values may round one unit of the last place apart: 1/128 of
            # the largest at most
            check(err <= (1 / 128 if name in names[:4] else 1e-3) * scale,
                  "kda: %s of the kernels is %.3g from %s (largest |value| "
                  "%.3g)" % (name, err, what, scale))

    key_w, key = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31))
    xs, do = inputs(*witness, key_w)
    log("kda: %d tokens, %d heads of %d, against the recurrence token by "
        "token" % (witness + (head_dim,)))
    t = time.time()
    got = both(delta_rule.kda, do)(*xs)
    want = both(_recurrence, do)(*(x.astype(jnp.float32) for x in xs))
    log("kda: kernels and the recurrence %.1fs" % (time.time() - t))
    compare("the recurrence", got, want)

    xs, do = inputs(seq, heads, key)
    log("kda: %d tokens, %d heads of %d" % (seq, heads, head_dim))
    t = time.time()
    got = both(delta_rule.kda, do)(*xs)
    _check_placed(list(got), platform, "kda: results")
    want = both(delta_rule.kda_chunked, do)(*xs)
    log("kda: kernels and chunked form %.1fs" % (time.time() - t))
    compare("the chunked form", got, want)
    return seq


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------

class _KeepFutures:
    """A ContinuousBatcher that remembers the futures it handed out, so the
    answers of a load test can be read back (``poisson_loadtest`` only
    counts them)."""

    def __init__(self, batcher):
        self._batcher = batcher
        self.futures = []

    def submit(self, *args, **kwargs):
        fut = self._batcher.submit(*args, **kwargs)
        self.futures.append(fut)
        return fut

    def __getattr__(self, name):
        return getattr(self._batcher, name)


def serve(buckets, image_size, n_requests, qps, n_check, classes=1000,
          seed=0):
    """ResNet-50 through ``ServeEngine`` + ``ContinuousBatcher`` under
    open-loop Poisson traffic; ``n_check`` of the answers against a float32
    forward of the same net on the host's cpu device."""
    import jax
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.serve import (ContinuousBatcher, ServeEngine,
                                           poisson_loadtest)

    mx.random.seed(seed)
    net = vision.resnet50_v1(classes=classes)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))
    eng = ServeEngine(net, buckets=buckets, lint="error")
    t = eng.warmup(np.zeros((3, image_size, image_size), np.float32))
    log("serve: buckets %s warm: trace %.1fs + compile %.1fs"
        % (list(buckets), t["trace"], t["compile"]))
    pool = np.random.RandomState(seed).rand(
        n_check, 3, image_size, image_size).astype(np.float32)
    batcher = _KeepFutures(ContinuousBatcher(eng, max_delay=0.010))
    try:
        rep = poisson_loadtest(batcher, lambda i, rng: pool[i % n_check],
                               qps=qps, n_requests=n_requests, seed=seed)
        answers = [np.asarray(f.result(timeout=30.0))
                   for f in batcher.futures[:n_check]]
    finally:
        batcher.close()
    log("serve: " + rep.format())
    check(rep.ok == n_requests and len(batcher.futures) == n_requests,
          "serve: %d of %d requests answered" % (rep.ok, n_requests))
    check(rep.errors == 0 and rep.shed == 0 and rep.hung == 0,
          "serve: %d errors, %d shed, %d hung"
          % (rep.errors, rep.shed, rep.hung))
    check(rep.recompiles == 0, "serve: %d recompiles after the warm-up"
          % rep.recompiles)

    # the reference: the same weights moved to the host's cpu device and
    # run there op by op in float32 (true f32 products — the chip's
    # default matmul precision is lower, hence cosine and not allclose)
    cpu = jax.devices("cpu")[0]
    for p in net.collect_params().values():
        p.set_data(jax.device_put(p.data().asnumpy(), cpu))
    with jax.default_device(cpu):
        ref = net(nd.array(jax.device_put(pool, cpu))).asnumpy()
    got = np.stack(answers).reshape(ref.shape)
    check(np.isfinite(got).all(), "serve: an answer is not finite")
    cos = [float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
           for a, b in zip(got, ref)]
    top_got, top_ref = got.argmax(1), ref.argmax(1)
    log("serve: %d answers vs float32 on %s: cosine min %.6f, top-1 %s vs "
        "%s" % (n_check, cpu, min(cos), top_got.tolist(), top_ref.tolist()))
    check(min(cos) >= 0.999, "serve: cosine %r below 0.999" % (cos,))
    check((top_got == top_ref).all(), "serve: top-1 differs: %r vs %r"
          % (top_got.tolist(), top_ref.tolist()))
    return rep


# ---------------------------------------------------------------------------
# --multichip: dp=4 ZeRO-1 step against the one-chip step
# ---------------------------------------------------------------------------

def multichip(dp, batch, image_size, steps, platform, classes=1000, seed=0,
              loss_rtol=MULTICHIP_LOSS_RTOL, **step_kwargs):
    """The data-parallel step ``bench.py --mesh-dp`` builds —
    ``make_train_step(mesh=dp, zero=1)`` — for ``steps`` steps, and the
    one-chip step on the same batch and seed for the first of them."""
    import jax
    import numpy as np

    import bench

    x, y = _batch(batch, image_size, classes, seed)

    net_one, one = bench.build_train_step(image_size=image_size,
                                          classes=classes, seed=seed,
                                          **step_kwargs)
    one.aot_compile(x, y)
    loss_one, _ = _run_steps(one, x, y, 1)
    log("multichip: one-chip step, first loss %.5f" % loss_one[0])
    # its state leaves device 0 before the mesh step arrives
    del net_one, one
    gc.collect()

    mesh = bench.dp_mesh(dp)
    _, step = bench.build_train_step(image_size=image_size, classes=classes,
                                     mesh=mesh, zero=1, seed=seed,
                                     **step_kwargs)
    times = step.aot_compile(x, y)
    log("multichip: dp=%d zero=1 step: trace %.1fs, compile %.1fs"
        % (dp, times["trace"], times["compile"]))
    text = step.compiled.as_text()
    ops = {op: text.count(op + "(") + text.count(op + "-start(")
           for op in ("all-reduce", "reduce-scatter", "all-gather")}
    log("multichip: collectives in the compiled step: %r" % ops)
    check(ops["all-reduce"] + ops["reduce-scatter"] > 0,
          "multichip: no all-reduce or reduce-scatter in the compiled step")
    check(ops["all-gather"] > 0,
          "multichip: no all-gather in the compiled step (ZeRO-1 gathers "
          "the updated shards)")

    losses, ms = _run_steps(step, x, y, steps)
    log("multichip: losses %s; median step %.2f ms on %d x %s"
        % (" ".join("%.5f" % v for v in losses), statistics.median(ms), dp,
           jax.devices()[0].device_kind))
    _check_losses(losses, "multichip")
    rel = abs(losses[0] - loss_one[0]) / abs(loss_one[0])
    log("multichip: first-step loss dp=%d %.5f vs one chip %.5f "
        "(rel diff %.2e, tolerance %g)"
        % (dp, losses[0], loss_one[0], rel, loss_rtol))
    check(rel <= loss_rtol,
          "multichip: first-step loss differs by %.3e" % rel)

    params, state = _state_arrays(step)
    devs = set()
    for a in params:
        shards = a.addressable_shards
        devs |= {s.device for s in shards}
        check(len({s.device for s in shards}) == dp
              and all(s.data.shape == a.shape for s in shards),
              "multichip: a parameter %r is not replicated over %d devices"
              % (a.shape, dp))
    check(len(devs) == dp and {d.platform for d in devs} == {platform},
          "multichip: parameters live on %r" % (devs,))
    per_dev = {d: 0 for d in devs}
    for a in state:
        shards = a.addressable_shards
        check(len({s.device for s in shards}) == dp
              and all(s.data.shape[0] * dp == a.shape[0] for s in shards),
              "multichip: an optimizer-state leaf %r is not split %d ways "
              "on its leading dim" % (a.shape, dp))
        for s in shards:
            per_dev[s.device] += s.data.nbytes
    total = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in state)
    log("multichip: %d params replicated on %d devices; optimizer state "
        "%.1f MB in all, per device %s MB"
        % (len(params), dp, total / 1e6,
           sorted(round(v / 1e6, 1) for v in per_dev.values())))
    check(all(v * dp == total for v in per_dev.values()),
          "multichip: a device does not hold 1/%d of the optimizer state: "
          "%r of %d" % (dp, per_dev, total))
    return losses


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the dp=4 ZeRO-1 step and its one-chip "
                         "comparison; needs four tpu devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device("tpu", 4 if args.multichip else 1)
    if args.multichip:
        multichip(dp=4, batch=256, image_size=224, steps=3, platform="tpu",
                  seed=args.seed)
    else:
        train(batch=256, image_size=224, steps=8, platform="tpu",
              seed=args.seed)
        # the shapes of smallthinker_21b_train_16k and trinity_mini_train_8k
        experts(98304, 2560, 768, 8, "relu", platform="tpu", seed=args.seed)
        experts(65536, 2048, 1024, 16, "silu", platform="tpu", seed=args.seed)
        # the shapes of kimi_linear_train_16k's KDA layers
        kda(16384, 32, 128, platform="tpu", seed=args.seed)
        serve(buckets=(16, 64), image_size=224, n_requests=64, qps=100.0,
              n_check=8, seed=args.seed)
    log("all phases passed")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
