"""Runner ``train_decoder``: a decoder's train step on one fixed, seeded batch
of token ids, for ANY decoder family.

The window, the set-up and the comparison are ``train_tokens``'s (whole
chunks of ``chunk_steps`` steps with one host sync a chunk; nothing compiled
in the window; every array on the chip; the loss must fall; no second copy
of the weights; ``correct`` decided from the TIMED step object's first
``reference.steps`` losses and the gradient it applied in step 1, leaf by
leaf, against the plain reference, which runs AFTER the window).  What is a
family's own is taken from the family's reference module,
``perfbench/references/<reference.module>.py``:

* ``model_cfg(config)``: what the reference needs of the configuration;
* ``Blocks``, ``balance``, ``gradients``, ``apply``, ``step``: the reference;
* ``counters(config, loads, batch)``: the work and byte counts of the
  family's kernels, from the shapes and the step's OWN routing counts;
* ``GRAD_GROUPS``: how the gradient's leaves are grouped for the limits of
  ``reference.grad_rel``;
* ``CONTROLS``: the ways to break the step on purpose, each ``(registered
  op, what replaces it given what it was)``.

So another decoder brings a zoo module, a reference module and data files,
and no runner.  The configuration names its loss (``loss``, a class of
``gluon.loss``) and, optionally, ``loss_kwargs``.

``python3 perfbench/runners/train_decoder.py --workload <cell> --seed <n>
--control <names>`` runs the same comparison on a step broken in each of the
named ways, and each must come out not correct (PERF.md section 4).
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import math
import os
import sys
import time

if __name__ == "__main__":  # the controls' command line: run from the root
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from perfbench import checks, hlo_tag
from perfbench.runners import train as _train
from perfbench.runners.train_tokens import (
    Weights, _leaf_errors, applied_gradient, build_net, log, routing_counts,
    seeded_batch, short_names, step_choices)

#: the keys a configuration of this runner holds besides the common ones
CONFIG_KEYS = ("seq_len", "vocab_rows", "experts_held", "num_layers", "loss")

_factory = _train._factory

#: the pre-pass that sets the selection biases (``SetUp``): iterations, rate,
#: decay of the reference's ``balanced_bias``
_BALANCE = (40, 0.1, 0.9)


def abstract_sample(config):
    """One sample as the net takes it, abstractly: one sequence of ids."""
    import jax

    return jax.ShapeDtypeStruct((1, config["seq_len"]), "int32")


def make_step(net, config, seed):
    """``make_train_step`` over ``net`` with the loss, the recipe and the
    precision the configuration's file states."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(seed)
    recipe, prec = config["recipe"], config["precision"]
    loss = getattr(gluon.loss, config["loss"])(**config.get("loss_kwargs", {}))
    return make_train_step(
        net, loss, optimizer=recipe["optimizer"],
        learning_rate=recipe["learning_rate"], beta1=recipe["beta1"],
        beta2=recipe["beta2"], epsilon=recipe["epsilon"], wd=recipe["wd"],
        multi_precision=prec["multi_precision"],
        loss_scale=prec["loss_scale"], compute_dtype=prec["compute_dtype"])


@contextlib.contextmanager
def control(controls, name):
    """Break the program the way ``controls[name]`` says (a reference
    module's ``CONTROLS``) while a step is built and traced under it;
    ``None`` breaks nothing."""
    from incubator_mxnet_tpu.ops import registry

    if name is None:
        yield
        return
    target, replace = controls[name]
    op = registry.OPS[target]
    was = op.fn
    op.fn = replace(was)
    try:
        yield
    finally:
        op.fn = was


def compare(config, groups, losses, errors, ref_losses, routing):
    """The numbers ``correct`` is decided from, each ``[value, limit]``, and
    what is wrong where one is over its limit.  ``groups`` is the reference
    module's ``GRAD_GROUPS``; ``routing`` holds ``route_refused_share`` and
    ``route_moved_share`` (see ``SetUp.reference``)."""
    lim = config["reference"]
    compared, problems = {}, []
    for i, (a, b) in enumerate(zip(losses, ref_losses)):
        rel = abs(a - b) / max(abs(b), 1e-30)
        compared["ref_loss%d_rel" % i] = [rel, lim["loss_rtol"][i]]
    for name, value in routing.items():
        compared[name] = [value, lim[name]]
    worst = {}
    for leaf, err in errors.items():
        group = next(g for g, pattern in groups if pattern.search(leaf))
        if err >= worst.get(group, ("", -1.0))[1]:
            worst[group] = (leaf, err)
    for group in (g for g, _ in groups if g in worst):
        leaf, err = worst[group]
        compared["grad_worst_%s" % group] = [err, lim["grad_rel"][group]]
        print("compared: worst %s gradient leaf %s %.4e" % (group, leaf, err),
              flush=True)
    for name, (value, limit) in compared.items():
        if not value <= limit:
            problems.append("reference: %s = %.4e (> %g)" % (name, value,
                                                              limit))
    return compared, problems


class SetUp:
    """The batch on the device, the net with its shapes, its weights as a
    function of the seed, and the two sides of the comparison: the step's
    (``first_steps``, before the window) and the plain reference's
    (``reference``, once the step has left the device)."""

    def __init__(self, cell, seed, t_start, lap=lambda name: None):
        import jax

        from incubator_mxnet_tpu import nd

        self.config, self.seed, self.t_start = cell["config"], seed, t_start
        config = self.config
        batch = config["recipe"]["per_chip_batch"] * cell["chips"]
        x, y = seeded_batch(config, seed, batch)
        self.x, self.y = nd.array(x, dtype="int32"), nd.array(y,
                                                              dtype="int32")
        lap("data_s")
        self.net = build_net(config, batch)
        self.trained = [name for p, name in short_names(self.net).items()
                        if p.grad_req != "null"]
        self.weights = Weights(self.net, seed)
        lap("build_s")
        self.ref = importlib.import_module(
            "perfbench.references." + config["reference"]["module"])
        self.cfg = self.ref.model_cfg(config)
        self.blocks = self.ref.Blocks(self.cfg, config["recipe"])
        self._later_losses = None
        # the selection biases: what evens out each router's load on the
        # run's batch at the seed's weights, from ONE forward pass of the
        # plain reference (why: train_tokens.SetUp).  It fixes where the
        # held share STARTS.
        bias = self.ref.balance(self.reference_params(), self.x._data,
                                self.cfg, *_BALANCE, blocks=self.blocks)
        # on the host: a step donates the arrays it is given
        self.weights.fixed.update(jax.device_get(bias))
        log(t_start, "selection biases from the reference's forward pass: "
            "largest %.3f" % max(float(abs(b).max())
                                 for b in self.weights.fixed.values()))
        lap("reference_s")

    def reference_params(self):
        """The seed's weights as the plain reference takes them."""
        return {k: v for k, v in self.weights.by_name().items()
                if not k.endswith(("_counts", "_chosen"))}

    def first_steps(self, broken=None):
        """The TIMED step object, built while ``control(broken)`` holds, and
        its side of the comparison: ``(step, aot_compile's times, the losses
        of its first reference.steps steps, the gradient it applied in the
        first and the experts its routers chose in the first, on the
        host)``."""
        config = self.config
        self.release()
        self.weights.install()
        with control(self.ref.CONTROLS, broken):
            step = make_step(self.net, config, self.seed)
            times = step.aot_compile(self.x, self.y)
        # the fused step keeps its gradients inside the program; the
        # imperative buffers (one float32 zero array a parameter) would only
        # take the room the step's temporaries need
        self.net.collect_params().setattr("grad_req", "null")
        mem = step.compiled.memory_analysis()
        log(self.t_start, "step program%s: trace %.2fs compile %.2fs; the "
            "compiler counts %.3f GB arguments + %.3f GB outputs + %.3f GB "
            "temporaries - %.3f GB aliased per device"
            % (" (control: %s)" % broken if broken else "", times["trace"],
               times["compile"], mem.argument_size_in_bytes / 1e9,
               mem.output_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9,
               mem.alias_size_in_bytes / 1e9))
        losses, applied, chosen = [], None, None
        for i in range(config["reference"]["steps"]):
            losses.append(float(step(self.x, self.y).asscalar()))
            log(self.t_start, "step %d: loss %.6f" % (i, losses[-1]))
            if i == 0:
                applied = applied_gradient(step, self.trained, config)
                chosen = step_choices(self.net)
        return step, times, losses, applied, chosen

    def release(self):
        """Take the net's arrays off the device and make its parameters
        trainable again, so that the reference has the room, or another
        step can be built over the net.  The caller drops its step."""
        for p, name in short_names(self.net).items():
            p._data = None
            if name in self.trained:
                p.grad_req = "write"
        gc.collect()

    def reference(self, losses, applied, chosen):
        """The plain reference's side, and the verdict: ``(compared,
        problems)``.  The reference routes by its own float32 scores and
        follows the step's choice for a token only where that choice is a
        top-k within ``reference.route_eps`` of its own scores (the
        reference's ``route``): ``route_refused_share`` is the share of
        tokens whose choice was not, ``route_moved_share`` the share of the
        step's assignments its own top-k did not make (each the worst expert
        layer's).  Step 0's loss and gradient are taken so; the later steps
        route freely and are the same for every step compared, so they are
        run once."""
        import jax.numpy as jnp
        import numpy as np

        config, ref, cfg = self.config, self.ref, self.cfg
        lim = config["reference"]
        x, y = self.x._data, self.y._data
        p = self.reference_params()
        forced = {i: jnp.asarray(c) for i, c in chosen.items()}
        later = self._later_losses is None
        sweep = ref.gradients(p, x, y, cfg, forced, lim["route_eps"],
                              self.blocks)
        loss0, facts = next(sweep)
        log(self.t_start, "reference: loss 0 = %.6f" % float(loss0))
        errors, m, v = {}, {}, {}
        for group in sweep:
            for name, grad in group.items():
                errors.update(_leaf_errors(name, jnp.asarray(applied[name]),
                                           grad))
            if later:
                ref.apply(p, m, v, 1, group, self.blocks)
        routing = {}
        if forced:
            refused = np.asarray(facts["refused"])
            log(self.t_start, "reference: shares of the tokens whose choice "
                "needs more than %g x 1/4, 1/2, 1, 2, each expert layer: %s"
                % (lim["route_eps"], " ".join(
                    "/".join("%.2e" % v for v in row) for row in refused)))
            routing = {"route_refused_share": float(refused[:, 2].max()),
                       "route_moved_share": max(map(float, facts["moved"]))}
        if later:
            self._later_losses = []
            for i in range(1, lim["steps"]):
                if i + 1 < lim["steps"]:
                    value = ref.step(p, m, v, i + 1, x, y, cfg, self.blocks)
                else:       # the last loss needs no gradient
                    value, _ = next(ref.gradients(p, x, y, cfg,
                                                  blocks=self.blocks))
                self._later_losses.append(float(value))
                log(self.t_start, "reference: loss %d = %.6f"
                    % (i, self._later_losses[-1]))
        del p, m, v
        gc.collect()
        return compare(config, ref.GRAD_GROUPS, losses, errors,
                       [float(loss0)] + self._later_losses, routing)


def run(cell, platform, seed, seconds, trace, t_start, counter):
    """Set up, check, measure; ``run.py::result_line`` reads the facts."""
    import jax

    from incubator_mxnet_tpu import _backend

    config, traffic = cell["config"], cell["traffic"]
    parts = {}
    mark = time.monotonic()

    def lap(name):
        nonlocal mark
        now = time.monotonic()
        parts[name] = now - mark
        mark = now

    parts["import_s"] = mark - t_start
    cache_dir = _backend.use_compile_cache()
    devices = jax.devices()[:cell["chips"]]
    batch = config["recipe"]["per_chip_batch"] * cell["chips"]
    chunk_steps = traffic["chunk_steps"]
    log(t_start, "%s: %s, %d x %d tokens on %d x %s, compile cache %s"
        % (cell["name"], config["name"], batch, config["seq_len"],
           len(devices), devices[0].device_kind, cache_dir))

    setup = SetUp(cell, seed, t_start, lap)
    step, times, first, applied, chosen = setup.first_steps()
    net, x, y = setup.net, setup.x, setup.y
    lap("first_steps_s")
    # what aot_compile() itself reports for the timed step, as the train
    # runner reports it; first_steps_s holds them too, and reference_s the
    # reference's forward pass that sets the selection biases
    parts["trace_s"], parts["compile_s"] = times["trace"], times["compile"]

    # executed warm-up: one whole chunk, counted as set-up
    losses = _train._chunk(step, x, y, chunk_steps, annotate=False)
    n_warm = len(losses)
    lap("warmup_s")

    with counter:
        n_steps = 0
        t0 = t = time.monotonic()
        setup_s = t0 - t_start
        while t - t0 < seconds:
            losses += _train._chunk(step, x, y, chunk_steps, annotate=False)
            n_steps += chunk_steps
            t = time.monotonic()
        window_s = t - t0
    built = counter.count
    samples_per_s = n_steps * batch / window_s

    summary = None
    if trace:
        kinds = hlo_tag.kinds_from_hlo(step.compiled.as_text())
        more, summary = _train.traced_chunk(step, x, y, chunk_steps, kinds)
        losses += more
        if summary:
            log(t_start, "traced %d x %s: busy %.4fs of %.4fs; heaviest ops "
                "%s" % (summary["n_modules"], summary["module"],
                        summary["busy_s"], summary["window_s"],
                        summary["device_ops"][:5]))

    values = [float(v.asscalar()) for v in losses]
    in_window = values[n_warm:n_warm + n_steps]
    params = [p.data()._data for p in net.collect_params().values()]
    state = jax.tree.leaves(step.opt_state)
    off, n_arrays = checks.off_device(params + state, platform), \
        len(params + state)
    counts = setup.ref.counters(config, routing_counts(net), batch)
    # the step and its state leave the device; the plain reference takes
    # their room, outside set-up and outside the window
    compiled = step.compiled
    del step, params, state, losses
    setup.release()
    mark = time.monotonic()
    compared, problems = setup.reference(first, applied, chosen)
    lap("reference_after_window_s")
    what = checks.losses_problem(first + values, first[0],
                                 in_window[-chunk_steps:], True)
    if what:
        problems.append(what)
    if built:
        problems.append("%d XLA program(s) built inside the window" % built)
    if off:
        problems.append("%d of %d parameter and state arrays are not on %s "
                        "devices" % (len(off), n_arrays, platform))
    if counts["assignments_dropped"]:
        problems.append("%d assignments were dropped"
                        % counts["assignments_dropped"])
    compared.update({
        "nonfinite_losses": [
            sum(1 for v in first + values if not math.isfinite(v)), 0],
        "last_chunk_min_loss_over_first": [
            min(in_window[-chunk_steps:]) / first[0], 1.0],
        "programs_built_in_window": [built, 0],
        "arrays_off_device": [len(off), 0],
        "assignments_dropped": [counts["assignments_dropped"], 0],
    })

    log(t_start, "window: %d steps of %d x %d tokens in %.3fs = %.4f "
        "samples/s; losses first %.4f, last chunk %s"
        % (n_steps, batch, config["seq_len"], window_s, samples_per_s,
           first[0], " ".join("%.4f" % v for v in in_window[-chunk_steps:])))
    log(t_start, "routing in the last step: %d assignments, %d on the held "
        "experts (%.4f of them), largest over mean load %.3f; FLOP a sample "
        "%.4g" % (counts["assignments_routed"], counts["assignments_held"],
                  counts["assignments_held"] / counts["assignments_routed"],
                  counts["moe_load_max_over_mean"],
                  counts["flops_per_sample"]))
    if cell["peaks"]:
        peak = cell["peaks"]["bf16_flops_per_s"]
        log(t_start, "MFU %.2f %% (%.4f samples/s x %.4g FLOP / (%d x %.4g))"
            % (100 * samples_per_s * counts["flops_per_sample"]
               / (len(devices) * peak), samples_per_s,
               counts["flops_per_sample"], len(devices), peak))
    log(t_start, "parts: %s" % " ".join(
        "%s=%.2f" % kv for kv in parts.items()))
    return {
        "problems": problems,
        "compared": compared,
        "attempted": n_steps,
        "failed": sum(1 for v in in_window if not math.isfinite(v)),
        "setup_s": setup_s,
        "end_to_end": {"train_samples_per_s": samples_per_s},
        "setup_parts": parts,
        "counters": counts,
        "trace": summary,
        "devices": devices,
        "programs": [compiled],
    }


def main(argv=None) -> int:
    """Controls: the cell's set-up, then its comparison on a step broken in
    each of the ways ``--control`` names (``none``: on the sound step; the
    default: every control of the family's reference module).  One JSON
    line a control; exit code 0 if every one came out NOT correct, as it
    must."""
    import argparse
    import json

    from incubator_mxnet_tpu import _backend
    from perfbench import run as bench_run

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--control", default=None,
                    help="comma-separated names of the reference module's "
                    "CONTROLS, or none")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    cell = bench_run.load_cell(bench_run.ROOT, args.workload)
    checks.require_devices("tpu", cell["chips"])
    _backend.use_compile_cache()
    setup = SetUp(cell, args.seed, t_start)
    names = args.control.split(",") if args.control \
        else sorted(setup.ref.CONTROLS)
    passed_where_it_must_fail = 0
    for name in names:
        broken = None if name == "none" else name
        step, _, losses, applied, chosen = setup.first_steps(broken)
        del step
        setup.release()
        compared, problems = setup.reference(losses, applied, chosen)
        print(json.dumps({"control": name, "seed": args.seed,
                          "correct": not problems, "compared": compared}),
              flush=True)
        passed_where_it_must_fail += bool(broken) and not problems
    return 1 if passed_where_it_must_fail else 0


if __name__ == "__main__":
    sys.exit(main())
