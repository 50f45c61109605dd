"""Runner ``toy_tokens`` of the CPU tests: a next-token step of an embedding
and a dense layer over int32 ids, plain SGD, in ``jax.numpy`` alone.

It is NOT a runner of the benchmark and ``run.py`` does not look here: the
fixture ``toy_tokens_runner`` (``tests/benchmark_tests/conftest.py``)
installs it as ``perfbench.runners.toy_tokens`` for one test and removes it
again.  It proves that a cell whose inputs are token ids, with a
configuration that holds no ``image_size``, needs nothing but a runner file,
a configuration, a mix and an entry of ``workloads``.
"""
import time

import numpy as np

CONFIG_KEYS = ("seq_len", "vocab_size")


def abstract_sample(config):
    """One sample as the step takes it: a sequence of ids."""
    import jax

    return jax.ShapeDtypeStruct((1, config["seq_len"]), "int32")


def _loss(params, ids):
    import jax
    import jax.numpy as jnp

    table, dense = params
    logp = jax.nn.log_softmax(table[ids[:, :-1]] @ dense)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


def _reference_loss(params, ids):
    table, dense = (np.asarray(p, np.float64) for p in params)
    logits = table[ids[:, :-1]] @ dense
    logits -= logits.max(-1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    return -np.take_along_axis(logp, ids[:, 1:, None], -1).mean()


def run(cell, platform, seed, seconds, trace, t_start, counter):
    import jax

    config, recipe = cell["config"], cell["config"]["recipe"]
    batch, vocab = recipe["per_chip_batch"], config["vocab_size"]
    width = config["factory_kwargs"]["width"]
    k_ids, k_table, k_dense = jax.random.split(
        jax.random.PRNGKey(seed % (2 ** 32)), 3)
    ids = jax.random.randint(k_ids, (batch, config["seq_len"]), 0, vocab)
    params = (0.1 * jax.random.normal(k_table, (vocab, width)),
              0.1 * jax.random.normal(k_dense, (width, vocab)))
    t0 = time.monotonic()
    want = _reference_loss(params, np.asarray(ids))
    t1 = time.monotonic()

    def step(params, ids):
        loss, grads = jax.value_and_grad(_loss)(params, ids)
        return tuple(p - recipe["learning_rate"] * g
                     for p, g in zip(params, grads)), loss

    lowered = jax.jit(step).lower(params, ids)
    t2 = time.monotonic()
    compiled = lowered.compile()
    t3 = time.monotonic()
    params, first = compiled(params, ids)   # the executed warm-up
    first, n_steps = float(first), 0
    with counter:
        t_open = t = time.monotonic()
        while t - t_open < seconds:
            params, loss = compiled(params, ids)
            loss.block_until_ready()
            n_steps += 1
            t = time.monotonic()
    rel = abs(first - want) / want
    problems = []
    if not rel <= config["reference"]["rtol"][0]:
        problems.append("reference: loss 0 differs by %.3e" % rel)
    if not float(loss) < first:
        problems.append("the loss did not fall: %r -> %r" % (first, loss))
    if counter.count:
        problems.append("%d program(s) built in the window" % counter.count)
    return {
        "problems": problems, "attempted": n_steps, "failed": 0,
        "compared": {"ref_loss0_rel": [rel, config["reference"]["rtol"][0]],
                     "last_loss_over_first": [float(loss) / first, 1.0],
                     "programs_built_in_window": [counter.count, 0]},
        "setup_s": t_open - t_start,
        "end_to_end": {
            "train_samples_per_s": n_steps * batch / (t - t_open)},
        "setup_parts": {"reference_s": t1 - t0, "trace_s": t2 - t1,
                        "compile_s": t3 - t2},
        "counters": {"flops_per_sample": 6 * config["fwd_macs_per_sample"]},
        "trace": None, "devices": jax.devices()[:cell["chips"]],
        "programs": [compiled],
    }
