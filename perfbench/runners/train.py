"""Runner ``train``: a configuration's train step on one fixed, seeded batch.

The step is built the way ``bench.build_train_step`` builds it (zoo net from
the seed, ``make_train_step``, ``aot_compile``), from the configuration's
file alone; the traffic file says the mesh, ZeRO and the steps in a chunk.

A window is whole chunks of ``chunk_steps`` steps, dispatched back to back,
with ONE host sync (``wait_to_read`` on the chunk's last loss) per chunk;
the clock runs from the sync that opens the window to the sync that closes
the last whole chunk, and a new chunk starts while less than ``seconds``
have passed.  ``run()`` is a plain function of its files and a platform
name, so the tests call it at a tiny size on the CPU.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import math
import re
import shutil
import tempfile
import time

from perfbench import checks, hlo_tag, trace_reduce

#: the keys a configuration of this runner holds besides the common ones
CONFIG_KEYS = ("image_size", "channels", "classes", "loss", "initializer")


def abstract_sample(config):
    """One sample as the net takes it, abstractly: a batch of one image."""
    import jax

    size = config["image_size"]
    return jax.ShapeDtypeStruct((1, config["channels"], size, size),
                                "float32")


def log(t_start, msg):
    print("[train %6.1fs] %s" % (time.monotonic() - t_start, msg), flush=True)


def _factory(spec: str):
    module, func = spec.split(":")
    return getattr(importlib.import_module(module), func)


#: The zoo's initialiser jits a function of no arguments
#: (``gluon.parameter._bulk_materialize``), so its key is a constant of the
#: program and every new seed would compile the program anew (10 s for
#: ResNet-50, 6 s for VGG-16; my chip run, PR 25).  The net is therefore
#: always initialised from this one seed, and ``Weights`` makes the run's
#: weights from ``--seed``.
_INIT_SEED = 0


def build_net(config):
    """The configuration's zoo net with its own initialiser's draw."""
    import incubator_mxnet_tpu as mx

    mx.random.seed(_INIT_SEED)
    net = _factory(config["factory"])(**config.get("factory_kwargs", {}))
    net.initialize(init=getattr(mx.init, config["initializer"])())
    size = config["image_size"]
    net.shape_init((1, config["channels"], size, size))
    return net


def _reseed(vals, key):
    import jax

    keys = jax.random.split(key, len(vals))
    return [v * jax.random.rademacher(k, v.shape, v.dtype) if v.ndim >= 2
            else v + 0 for v, k in zip(vals, keys)]


def _copies(vals):
    return [v + 0 for v in vals]


class Weights:
    """The run's weights and state, made from ``--seed`` in one jitted call
    whose key is an argument: every tensor the initialiser drew at random
    (the ones of two or more dimensions; it draws them uniform and symmetric
    about zero) gets a seeded random sign on each element, which is again a
    draw of the same distribution.  They are kept aside on the device, so
    that several steps, which donate and overwrite what they are given, can
    each start from them without building the net again."""

    def __init__(self, net, seed):
        import jax

        self._params = list(net.collect_params().values())
        self._kept = jax.jit(_reseed)(
            [p.data()._data for p in self._params],
            jax.random.PRNGKey(seed % (2 ** 32)))

    def restore(self):
        import jax

        for p, v in zip(self._params, jax.jit(_copies)(self._kept)):
            p.set_data(v)


def make_step(net, config, seed, mesh=None, zero=0, float32_reference=False,
              lint=None):
    """``make_train_step`` over ``net`` with the recipe and the precision the
    configuration's file states, as ``bench.build_train_step`` calls it.
    ``float32_reference`` swaps the precision for float32 with no loss
    scale: the yardstick's reference step.  The seed is set again so that
    every step over this net draws the same key (the same dropout masks)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(seed)
    recipe, prec = config["recipe"], config["precision"]
    if float32_reference:
        prec = {"compute_dtype": "float32", "multi_precision": False,
                "loss_scale": None}
    extra = {} if lint is None else {"lint": lint}
    return make_train_step(
        net, getattr(gluon.loss, config["loss"])(),
        optimizer=recipe["optimizer"], learning_rate=recipe["learning_rate"],
        momentum=recipe["momentum"], wd=recipe["wd"], mesh=mesh,
        zero=zero if mesh is not None else 0,
        multi_precision=prec["multi_precision"],
        loss_scale=prec["loss_scale"], compute_dtype=prec["compute_dtype"],
        **extra)


def seeded_batch(config, seed, batch):
    """One synthetic batch drawn on the host from the seed (uniform pixels,
    uniform labels), as ``chip_smoke._batch`` draws its one."""
    import numpy as np

    rng = np.random.RandomState(seed % (2 ** 32))
    size = config["image_size"]
    x = rng.uniform(size=(batch, config["channels"], size, size))
    y = rng.randint(0, config["classes"], batch)
    return x.astype(np.float32), y.astype(np.float32)


def resident(x, y, mesh=None):
    """The batch where the step wants it: on the default device, or split
    over the mesh's ``dp`` axis (``make_train_step``'s batch axis), so that
    no step of the window moves it again."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from incubator_mxnet_tpu import nd

    if mesh is None:
        return nd.array(x), nd.array(y)
    split = NamedSharding(mesh, PartitionSpec("dp"))
    return (nd.NDArray(jax.device_put(x, split)),
            nd.NDArray(jax.device_put(y, split)))


def reference_check(net, weights, config, seed, mesh, zero, x, y, t_start):
    """The cell's own recipe against the float32 step on the same rows, from
    the same seeded weights: ``reference.steps`` losses each, loss ``i``
    within ``reference.rtol[i]``.  Loss 0 checks the forward pass, loss 1
    the gradient and the update.  Lint is off on both: neither is the
    program under test.  Returns the problems found and the numbers
    compared, each ``[value, limit]``."""
    import jax

    ref = config["reference"]
    rows, steps, rtol = ref["rows"], ref["steps"], ref["rtol"]
    xs, ys = resident(x[:rows], y[:rows])
    got = {}
    for side in ("float32", "cell"):
        is_ref = side == "float32"
        weights.restore()
        step = make_step(net, config, seed, None if is_ref else mesh, zero,
                         float32_reference=is_ref, lint="off")
        with (jax.default_matmul_precision("highest") if is_ref
              else contextlib.nullcontext()):
            got[side] = [float(step(xs, ys).asscalar()) for _ in range(steps)]
        del step
        gc.collect()
    rel = [abs(a - b) / max(abs(b), 1e-30)
           for a, b in zip(got["cell"], got["float32"])]
    log(t_start, "reference: %d rows, cell %s vs float32 %s, rel %s "
        "(tolerances %s)" % (rows, got["cell"], got["float32"],
                             ["%.2e" % r for r in rel], rtol))
    return (["reference: loss %d differs from float32 by %.3e (> %g)"
             % (i, r, tol) for i, (r, tol) in enumerate(zip(rel, rtol))
             if not r <= tol],
            {"ref_loss%d_rel" % i: [r, tol]
             for i, (r, tol) in enumerate(zip(rel, rtol))})


def _chunk(step, x, y, chunk_steps, annotate):
    """Dispatch one chunk, sync on its last loss; the losses as NDArrays."""
    import jax

    span = jax.profiler.TraceAnnotation if annotate else (
        lambda _name: contextlib.nullcontext())
    losses = []
    for _ in range(chunk_steps):
        with span("bench.dispatch"):
            losses.append(step(x, y))
    with span("bench.sync"):
        losses[-1].wait_to_read()
    return losses


def traced_chunk(step, x, y, chunk_steps, kinds):
    """One more chunk under the profiler, reduced and deleted."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            losses = _chunk(step, x, y, chunk_steps, annotate=True)
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if not trace.devices:
        # (the CPU tests: no /device:TPU plane, so no device metric at all)
        return losses, None
    # the step program is the module that took most of the traced time
    total = {}
    for name, _, dur in trace.devices[0].modules:
        total[name] = total.get(name, 0.0) + dur
    module = max(total, key=total.get)
    summary = trace_reduce.summarize(trace, "^" + re.escape(module) + "$",
                                     kinds)
    summary["module"] = module
    return losses, summary


def run(cell, platform, seed, seconds, trace, t_start, counter):
    """Set up, check, measure.  ``cell`` has ``config``, ``traffic``,
    ``chips`` and ``peaks`` (the device kind's row of peaks.json; None off
    the TPU); ``counter`` is the process's ``checks.CompileCounter``.
    Returns the facts ``run.py`` builds the result line from."""
    import jax

    from incubator_mxnet_tpu import _backend
    from incubator_mxnet_tpu.parallel import make_mesh

    config, traffic = cell["config"], cell["traffic"]
    parts, problems = {}, []
    mark = time.monotonic()

    def lap(name):
        nonlocal mark
        now = time.monotonic()
        parts[name] = now - mark
        mark = now

    parts["import_s"] = mark - t_start
    cache_dir = _backend.use_compile_cache()
    devices = jax.devices()[:cell["chips"]]
    mesh, zero = None, 0
    if traffic.get("mesh"):
        mesh = make_mesh(dict(traffic["mesh"]), devices=devices)
        zero = traffic.get("zero", 0)
    batch = config["recipe"]["per_chip_batch"] * cell["chips"]
    chunk_steps = traffic["chunk_steps"]
    log(t_start, "%s: %s batch %d on %d x %s, compile cache %s"
        % (cell["name"], config["name"], batch, len(devices),
           devices[0].device_kind, cache_dir))

    x, y = seeded_batch(config, seed, batch)
    lap("data_s")
    net = build_net(config)
    weights = Weights(net, seed)
    lap("build_s")
    found, compared = reference_check(net, weights, config, seed, mesh, zero,
                                      x, y, t_start)
    problems += found
    lap("reference_s")

    weights.restore()
    del weights
    x, y = resident(x, y, mesh)
    step = make_step(net, config, seed, mesh, zero)
    times = step.aot_compile(x, y)
    parts["trace_s"], parts["compile_s"] = times["trace"], times["compile"]
    mark = time.monotonic()
    mem = step.compiled.memory_analysis()
    log(t_start, "step program: trace %.2fs compile %.2fs; the compiler "
        "counts %.3f GB arguments + %.3f GB outputs + %.3f GB temporaries "
        "- %.3f GB aliased per device"
        % (times["trace"], times["compile"], mem.argument_size_in_bytes / 1e9,
           mem.output_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9,
           mem.alias_size_in_bytes / 1e9))

    # executed warm-up: one whole chunk, counted as set-up
    losses = _chunk(step, x, y, chunk_steps, annotate=False)
    n_warm = len(losses)
    lap("warmup_s")

    with counter:
        n_steps = 0
        t0 = t = time.monotonic()
        setup_s = t0 - t_start
        while t - t0 < seconds:
            losses += _chunk(step, x, y, chunk_steps, annotate=False)
            n_steps += chunk_steps
            t = time.monotonic()
        window_s = t - t0
    built = counter.count
    samples_per_s = n_steps * batch / window_s

    summary = None
    if trace:
        kinds = hlo_tag.kinds_from_hlo(step.compiled.as_text())
        more, summary = traced_chunk(step, x, y, chunk_steps, kinds)
        losses += more
        if summary:
            log(t_start, "traced %d x %s: busy %.4fs of %.4fs; heaviest ops "
                "%s; longest in flight %s"
                % (summary["n_modules"], summary["module"],
                   summary["busy_s"], summary["window_s"],
                   summary["device_ops"][:5], summary["async_ops"][:5]))

    values = [float(v.asscalar()) for v in losses]
    in_window = values[n_warm:n_warm + n_steps]
    what = checks.losses_problem(values, values[0],
                                 in_window[-chunk_steps:],
                                 config.get("loss_must_fall", True))
    if what:
        problems.append(what)
    if built:
        problems.append("%d XLA program(s) built inside the window" % built)
    params = [p.data()._data for p in net.collect_params().values()]
    state = jax.tree.leaves(step.opt_state)
    off = checks.off_device(params + state, platform)
    if off:
        problems.append("%d of %d parameter and state arrays are not on %s "
                        "devices" % (len(off), len(params + state), platform))
    compared.update({
        "nonfinite_losses": [
            sum(1 for v in values if not math.isfinite(v)), 0],
        "last_chunk_min_loss_over_first": [
            min(in_window[-chunk_steps:]) / values[0],
            1.0 if config.get("loss_must_fall", True) else math.inf],
        "programs_built_in_window": [built, 0],
        "arrays_off_device": [len(off), 0],
    })

    flops_per_sample = 3 * 2 * config["fwd_macs_per_sample"]
    log(t_start, "window: %d steps of batch %d in %.3fs = %.2f samples/s; "
        "losses first %.4f, last chunk %s"
        % (n_steps, batch, window_s, samples_per_s, values[0],
           " ".join("%.4f" % v for v in in_window[-chunk_steps:])))
    if cell["peaks"]:
        peak = cell["peaks"]["bf16_flops_per_s"]
        log(t_start, "MFU %.2f %% (%.2f samples/s x %.4g FLOP / (%d x %.4g))"
            % (100 * samples_per_s * flops_per_sample / (len(devices) * peak),
               samples_per_s, flops_per_sample, len(devices), peak))
    log(t_start, "set-up parts: %s" % " ".join(
        "%s=%.2f" % kv for kv in parts.items()))
    return {
        "problems": problems,
        "compared": compared,
        "attempted": n_steps,
        "failed": sum(1 for v in in_window if not math.isfinite(v)),
        "setup_s": setup_s,
        "end_to_end": {"train_samples_per_s": samples_per_s},
        "setup_parts": parts,
        "counters": {
            "flops_per_sample": flops_per_sample,
            "flops_per_module_per_chip":
                flops_per_sample * config["recipe"]["per_chip_batch"],
        },
        "trace": summary,
        "devices": devices,
        "programs": [step.compiled],
    }
