"""Runner ``train_tokens``: a decoder's train step on one fixed, seeded batch
of token ids.

Built like ``train`` (the zoo's net from the configuration's file,
``make_train_step``, ``aot_compile``; whole chunks of ``chunk_steps`` steps
with one host sync a chunk; nothing compiled in the window; every array on
the chip; the loss must fall), with three differences that a model of this
size forces:

* NO second copy of the weights is kept on the device.  The run's weights
  are a pure function of ``--seed`` (``Weights``: one jitted call), made
  once for the plain reference and again for the step.
* ``correct`` is decided from the TIMED step object at the timed sizes: the
  losses of its first ``reference.steps`` steps against the plain reference
  (``perfbench/references/<name>.py``: float32, highest matmul precision,
  its own AdamW, its own routing), and the gradient it applied in step 1,
  leaf by leaf (the held experts' stacked matrices expert by expert), read
  back from AdamW's first moment (``m = (1 - beta1) g`` exactly after one
  step), against the reference's gradient.  Before the window only the
  step's side is taken (three losses, the first moment and the router's
  choices of step 1, copied to the host); the reference runs AFTER the
  window and the traced chunk, when the step and its state have left the
  device, so its seconds are not set-up.
* the work and byte counters of the attention and expert kernels are
  computed here, from the shapes and from the step's OWN routing counts
  (the expert layers' ``counts`` parameters, which the step writes).

``python3 perfbench/runners/train_tokens.py --workload <cell> --seed <n>
--control <window|expert|float8>`` runs the same comparison on a step that
was broken on purpose, and must come out not correct (PERF.md section 4).
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

#: the keys a configuration of this runner holds besides the common ones
CONFIG_KEYS = ("seq_len", "vocab_rows", "layer_types", "experts_held", "loss")

_factory = _train._factory


def abstract_sample(config):
    """One sample as the net takes it, abstractly: one sequence of ids."""
    import jax

    return jax.ShapeDtypeStruct((1, config["seq_len"]), "int32")


def log(t_start, msg):
    print("[tokens %6.1fs] %s" % (time.monotonic() - t_start, msg),
          flush=True)


# ---------------------------------------------------------------------------
# the net, its weights from the seed, the batch
# ---------------------------------------------------------------------------

def build_net(config, batch):
    """The configuration's zoo net with every shape resolved and nothing
    allocated: the family's parameters keep deferred initialisation, and the
    forward that resolves them is abstract."""
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import pure_forward
    from incubator_mxnet_tpu.gluon.parameter import shape_only_init

    net = _factory(config["factory"])(**config["factory_kwargs"])
    net.initialize(init=mx.init.Xavier())
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                       jax.ShapeDtypeStruct((batch, config["seq_len"]),
                                            "int32"))
    return net


def short_names(net):
    """``{parameter: its name without the net's prefix}``, the names the
    plain reference uses."""
    return {p: p.name[len(net.prefix):]
            for p in net.collect_params().values()}


def _draw(name, shape, key):
    """One parameter's seeded value.  Norm scales one; the selection bias
    zero (``SetUp`` sets it before the run); every matrix Xavier
    uniform, U(-a, a) with a = sqrt(6 / (inputs + outputs)), an expert's
    matrix from its own two widths; what the step writes (counts, chosen
    experts) zero."""
    import jax
    import jax.numpy as jnp

    if name.endswith("_gamma"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith(("_counts", "_chosen")):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("_bias"):
        return jnp.zeros(shape, jnp.float32)
    a = math.sqrt(6.0 / (shape[-1] + shape[-2]))
    return jax.random.uniform(key, shape, jnp.float32, -a, a)


class Weights:
    """The run's weights as a function of ``--seed``: ``make()`` is ONE
    jitted call whose key is an argument, so another seed is no other
    program, and calling it again gives the same arrays again.  ``fixed``
    holds what was worked out from them once and is handed out with them
    (the balanced selection biases, a few hundred numbers)."""

    def __init__(self, net, seed):
        import jax

        self._names = short_names(net)
        self._params = list(self._names)
        self._key = jax.random.PRNGKey(seed % (2 ** 32))
        specs = [(self._names[p], tuple(p.shape)) for p in self._params]

        def make(key):
            keys = jax.random.split(key, len(specs))
            return [_draw(name, shape, k)
                    for (name, shape), k in zip(specs, keys)]

        self._make = jax.jit(make)
        self.fixed = {}

    def by_name(self):
        return dict(zip((self._names[p] for p in self._params),
                        self._make(self._key)), **self.fixed)

    def install(self):
        by_name = self.by_name()
        for p in self._params:
            p.set_data(by_name[self._names[p]])


def seeded_batch(config, seed, batch):
    """Ids uniform over the held rows of the vocabulary, from the seed; the
    labels are the next tokens, so one more id is drawn than a sequence
    holds.  One document a sequence."""
    import numpy as np

    rng = np.random.RandomState(seed % (2 ** 32))
    ids = rng.randint(0, config["vocab_rows"],
                      (batch, config["seq_len"] + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def make_step(net, config, seed):
    """``make_train_step`` over ``net`` with the recipe and the precision
    the configuration's file states."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(seed)
    recipe, prec = config["recipe"], config["precision"]
    return make_train_step(
        net, getattr(gluon.loss, config["loss"])(),
        optimizer=recipe["optimizer"], learning_rate=recipe["learning_rate"],
        beta1=recipe["beta1"], beta2=recipe["beta2"],
        epsilon=recipe["epsilon"], wd=recipe["wd"],
        multi_precision=prec["multi_precision"],
        loss_scale=prec["loss_scale"], compute_dtype=prec["compute_dtype"])


# ---------------------------------------------------------------------------
# the plain reference's side of the comparison
# ---------------------------------------------------------------------------

def _layer_of(name):
    """3 for ``layer3_moe_chosen``."""
    return int(name.split("_")[0][len("layer"):])


def model_cfg(config):
    """What the plain reference needs of the configuration."""
    cfg = {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "rope_theta", "rms_norm_eps",
        "num_experts", "num_experts_per_tok", "route_norm", "route_scale",
        "mup_enabled", "layer_types", "num_dense_layers")}
    cfg["experts_held"] = tuple(config["experts_held"])
    return cfg


#: the pre-pass that sets the selection biases (``SetUp``): iterations, rate,
#: decay of ``references/<name>.py::balanced_bias``
_BALANCE = (40, 0.1, 0.9)


def step_choices(net):
    """``{layer: (tokens, K) int32}`` on the host: the experts the last
    step's router chose, as the step wrote them to the ``chosen``
    parameters."""
    import numpy as np

    return {_layer_of(name): np.asarray(p.data()._data).astype(np.int32)
            for p, name in short_names(net).items()
            if name.endswith("_moe_chosen")}


def _leaf_errors(name, got, want):
    """``{leaf: |got - want| / max(|got|, |want|)}`` of one parameter's
    gradient; an
    expert layer's stacked matrices (``..._moe_w1`` and its kin, one matrix
    an expert) are compared expert by expert, so that one expert's matrix
    cannot hide among sixteen."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), jnp.asarray(want, jnp.float32)
    axes = (1, 2) if got.ndim == 3 else None

    def norm(a):
        return jnp.sqrt(jnp.sum(a * a, axes))

    # over the larger of the two norms: a leaf that is zero on one side only
    # (an expert no token chose) reads 1, and one that is zero on both 0
    err = norm(got - want) / jnp.maximum(
        jnp.maximum(norm(got), norm(want)), 1e-30)
    if got.ndim == 3:
        return {"%s[%d]" % (name, n): float(e) for n, e in enumerate(err)}
    return {name: float(err)}


def applied_gradient(step, trained, config):
    """After the step's FIRST step: the gradient it applied, ``{leaf:
    numpy array}`` on the host, from AdamW's first moment (``m = (1 -
    beta1) g``).  ``trained`` names the trained parameters in the order of
    the optimizer's state."""
    import jax

    scale = 1.0 / (1.0 - config["recipe"]["beta1"])
    moments = jax.device_get([state[0] for state in step.opt_state])
    return {name: m * scale for name, m in zip(trained, moments)}


def compare(config, losses, errors, ref_losses, routing):
    """The numbers ``correct`` is decided from, each ``[value, limit]``, and
    what is wrong where one is over its limit.  ``routing`` holds
    ``route_refused_share`` and ``route_moved_share`` (see
    ``SetUp.reference``)."""
    lim = config["reference"]
    compared, problems = {}, []
    for i, (a, b) in enumerate(zip(losses, ref_losses)):
        rel = abs(a - b) / max(abs(b), 1e-30)
        compared["ref_loss%d_rel" % i] = [rel, lim["loss_rtol"][i]]
    for name, value in routing.items():
        compared[name] = [value, lim[name]]
    groups = {"attention": "_attn_", "experts": "_moe_w", "router": "_router_"}
    rest = dict(errors)
    for group, part in groups.items():
        mine = {k: rest.pop(k) for k in list(rest) if part in k}
        worst = max(mine, key=mine.get)
        compared["grad_worst_%s" % group] = [mine[worst],
                                             lim["grad_rel"][group]]
        print("compared: worst %s gradient leaf %s %.4e" % (
            group, worst, mine[worst]), flush=True)
    worst = max(rest, key=rest.get)
    compared["grad_worst_other"] = [rest[worst], lim["grad_rel"]["other"]]
    print("compared: worst other gradient leaf %s %.4e" % (worst, rest[worst]),
          flush=True)
    for name, (value, limit) in compared.items():
        if not value <= limit:
            problems.append("reference: %s = %.4e (> %g)" % (name, value,
                                                              limit))
    return compared, problems


# ---------------------------------------------------------------------------
# what the kernels have to do: operations and bytes, from shapes and counts
# ---------------------------------------------------------------------------

def admitted_pairs(seq, window):
    """Query-key pairs one head's causal mask admits over a sequence: all
    ``j <= i``, with ``window`` only ``i - window < j <= i``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def routing_counts(net):
    """``[(layer's short name, assignments per expert as floats)]`` of the
    expert layers, read from the ``counts`` parameters the step wrote in its
    last step."""
    import numpy as np

    return [(name, np.asarray(p.data()._data))
            for p, name in short_names(net).items()
            if name.endswith("_moe_counts")]


def counters(config, net, batch):
    """The work and byte counts the per-layer metrics read, per step on this
    chip.  Each counts the mathematics (what any implementation must do),
    never a kernel's own recomputation: a training step is the forward
    products and twice as many in the backward pass."""
    c = config
    seq, d, hd = c["seq_len"], c["hidden_size"], c["head_dim"]
    heads, f = c["num_attention_heads"], c["moe_intermediate_size"]
    first, count = c["experts_held"]
    tokens = batch * seq
    # attention: q k^T and p v over the admitted pairs, forward; the same two
    # and dq, dk, dv, dp (four) backward: six products of pairs x head_dim
    pairs = sum(admitted_pairs(seq, c["sliding_window"]
                               if kind == "sliding_attention" else None)
                for kind in c["layer_types"]) * heads * batch
    attn_fwd_macs = 2 * pairs * hd
    # experts: three matrices of d x f a held assignment, forward; twice that
    # backward.  The assignments are the step's own.
    loads = routing_counts(net)
    held = sum(float(counts[first:first + count].sum())
               for _, counts in loads)
    total = sum(float(counts.sum()) for _, counts in loads)
    expert_fwd_macs = held * 3 * d * f
    # bytes the grouped products must move: each held expert's three matrices
    # read in the forward pass, read again and their gradients written in the
    # backward pass (bf16), and each held row in and out of each product
    weight_bytes = len(loads) * count * 3 * d * f * 2 * 3
    row_bytes = held * (d + f + f + f + f + d) * 2 * 3
    # dispatch and combine: each held row of d gathered into expert order and
    # gathered back, forward, and the two transposes backward; read + written
    dispatch_bytes = held * d * 2 * 2 * 4
    dense_fwd_macs = config["fwd_macs_per_sample"] * batch
    worst = [float(counts[first:first + count].max()
                   / max(counts[first:first + count].mean(), 1e-30))
             for _, counts in loads]
    flops_per_sample = 3 * 2 * (dense_fwd_macs + attn_fwd_macs
                                + expert_fwd_macs) / batch
    return {
        "flops_per_sample": flops_per_sample,
        "flops_per_module_per_chip": flops_per_sample * batch,
        "attn_flops_per_module": 3 * 2 * attn_fwd_macs,
        "expert_flops_per_module": 3 * 2 * expert_fwd_macs,
        "expert_bytes_per_module": weight_bytes + row_bytes,
        "dispatch_bytes_per_module": dispatch_bytes,
        # a dispatch moves bytes and multiplies nothing: its compute side is
        # one operation a byte, so that the bytes bound it
        "dispatch_ops_per_module": dispatch_bytes,
        "assignments_held": held,
        "assignments_routed": total,
        "moe_load_max_over_mean": sum(worst) / max(len(worst), 1),
        "assignments_dropped": tokens * c["num_experts_per_tok"] * len(loads)
        - total,
    }


# ---------------------------------------------------------------------------
# the controls: the same comparison on a step broken on purpose
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def control(name):
    """Break the program the way ``name`` says while a step is built and
    traced under it; ``None`` breaks nothing.  ``window``: the sliding
    layers ignore their window.  ``expert``: the last held expert of every
    expert layer adds nothing (its output matrix is zero).  ``float8``: the
    expert product's inputs (rows and matrices) are rounded to float8's
    e4m3 under a scale a tensor in the forward pass, by
    ``lax.reduce_precision``: a cast there and back is something the
    compiler may drop (and on the chip it did)."""
    from incubator_mxnet_tpu.ops import registry

    if name is None:
        yield
        return
    import jax

    target = {"window": "_contrib_flash_attention", "expert":
              "_contrib_moe_experts", "float8": "_contrib_moe_experts"}[name]
    op = registry.OPS[target]
    was = op.fn

    @jax.custom_vjp
    def round8(a):
        # float8 as it is used: a scale a tensor, worked out in float32, so
        # that the largest magnitude is e4m3's largest (240 with 4 exponent
        # bits), 3 bits of mantissa
        wide = a.astype("float32")
        scale = jax.numpy.max(jax.numpy.abs(wide)) / 240
        return (jax.lax.reduce_precision(wide / scale, exponent_bits=4,
                                         mantissa_bits=3) * scale
                ).astype(a.dtype)

    # straight through: the cotangent passes unrounded.  (reduce_precision
    # transposes to itself, and a cotangent of 1e-6 under 4 exponent bits
    # and no scale is zero.)
    round8.defvjp(lambda a: (round8(a), None), lambda _, g: (g,))

    def broken(*args, **kwargs):
        if name == "window":
            kwargs["window"] = None
        elif name == "expert":
            args = args[:3] + (args[3].at[-1].set(0),) + args[4:]
        else:
            args = tuple(round8(a) for a in args[:4]) + args[4:]
        return was(*args, **kwargs)

    op.fn = broken
    try:
        yield
    finally:
        op.fn = was


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

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
        self.cfg = model_cfg(config)
        self.blocks = self.ref.Blocks(self.cfg, config["recipe"])
        self._later_losses = None
        # the selection biases: what evens out each router's load on the
        # run's batch at the seed's weights, from ONE forward pass of the
        # plain reference.  A random router at random weights sends a chip's
        # 16 experts 10-13 % of the assignments depending on the seed, and
        # the step's time follows (PERF.md section 6, PR 30); a trained
        # router is balanced.  It fixes where the held share STARTS.
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
        with control(broken):
            step = make_step(self.net, config, self.seed)
            times = step.aot_compile(self.x, self.y)
        # the fused step keeps its gradients inside the program; the
        # imperative buffers (one float32 zero array a parameter, 2.8 GB for
        # this family's share) would only take the room the step's
        # temporaries need
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
        top-k within ``reference.route_eps`` of its own scores
        (``references/<name>.py::route``): ``route_refused_share`` is the
        share of tokens whose choice was not, ``route_moved_share`` the
        share of the step's assignments its own top-k did not make (each
        the worst expert layer's).  Step 0's loss and gradient are taken so; the later
        steps route freely and are the same for every step compared, so
        they are run once."""
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
        return compare(config, losses, errors,
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
    counts = counters(config, net, batch)
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
    each of the ways ``--control`` names (``none``: on the sound step).
    One JSON line a control; exit code 0 if every one came out NOT correct,
    as it must."""
    import argparse
    import json

    from incubator_mxnet_tpu import _backend
    from perfbench import run as bench_run

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--control", default="window,expert,float8",
                    help="comma-separated: window, expert, float8, none")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    cell = bench_run.load_cell(bench_run.ROOT, args.workload)
    checks.require_devices("tpu", cell["chips"])
    _backend.use_compile_cache()
    setup = SetUp(cell, args.seed, t_start)
    passed_where_it_must_fail = 0
    for name in args.control.split(","):
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
