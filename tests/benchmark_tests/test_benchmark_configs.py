"""``fwd_macs_per_sample`` of each configuration ``BENCHMARK.json`` lists
against a count of the multiply-adds in the net's own forward pass, traced
abstractly on one sample as the configuration's runner describes it
(``abstract_sample``; nothing runs).  MFU and the roofline share rest on this
number."""
import importlib
import json
import os

import jax
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))


def _runner_of(config_name):
    """The runner of the cells that use the configuration: one, or the
    configuration's keys would have to serve two."""
    mixes = {w["traffic"] for w in _BENCH["workloads"]
             if w["config"] == config_name}
    runners = {json.load(open(os.path.join(
        _ROOT, _BENCH["paths"][0], "mixes", mix + ".json")))["runner"]
        for mix in mixes}
    assert len(runners) == 1, (config_name, runners)
    return importlib.import_module("perfbench.runners." + runners.pop())


def _macs(jaxpr) -> int:
    """Multiply-adds of every convolution and dot in ``jaxpr``, sub-jaxprs
    (pjit, custom_jvp, remat) included."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
            dn = eqn.params["dimension_numbers"]
            out = eqn.outvars[0].aval.shape
            in_per_group = rhs[dn.rhs_spec[1]]
            window = int(np.prod([rhs[i] for i in dn.rhs_spec[2:]]))
            total += int(np.prod(out)) * in_per_group * window
        elif eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"], None
            lhs = eqn.invars[0].aval.shape
            k = int(np.prod([lhs[i] for i in contract[0]]))
            total += int(np.prod(eqn.outvars[0].aval.shape)) * k
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _macs(sub)
    return total


@pytest.mark.parametrize(
    "entry", _BENCH["configs"],
    ids=[os.path.basename(c["file"]) for c in _BENCH["configs"]])
def test_fwd_macs_per_sample_matches_the_traced_forward(entry):
    from incubator_mxnet_tpu.gluon.block import pure_forward

    config = json.load(open(os.path.join(_ROOT, entry["file"])))
    runner = _runner_of(entry["name"])
    import incubator_mxnet_tpu as mx

    net = runner._factory(config["factory"])(**config["factory_kwargs"])
    net.initialize(init=mx.init.Xavier())
    sample = runner.abstract_sample(config)
    from incubator_mxnet_tpu.gluon.parameter import shape_only_init

    params = None

    def forward(vals, x):
        out, _ = pure_forward(net, params, vals, x, training=False)
        return out

    # deferred shapes resolve under an abstract forward; no initializer runs
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0], sample)
    params = list(net.collect_params().values())
    avals = [jax.ShapeDtypeStruct(tuple(p.shape), np.float32) for p in params]
    jaxpr = jax.make_jaxpr(forward)(avals, sample)
    counted = _macs(jaxpr.jaxpr)
    stated = config["fwd_macs_per_sample"]
    assert abs(counted - stated) / stated < 0.02, (counted, stated)
