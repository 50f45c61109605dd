"""The zoo's ResNets: the names their parameters carry, their residual blocks
against a plain ``jax.numpy`` block, and the keywords and the pass that left
with the ghost-BN kernels (PR 32).

Names: ``save_parameters`` writes a parameter under its place in the block
tree (``features.4.0.body.0.weight``) and ``collect_params`` under its global
name (``conv2d7_weight``: the N-th ``Conv2D`` built in this process).  Both,
with the shape, go into one digest a net; the digests were taken at commit
0b7d489, before the blocks were edited, so a checkpoint written there still
loads by name.  Global names are counted from the net's own first layer of
each kind, which is what does not depend on what else the process built.
"""
import hashlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from incubator_mxnet_tpu import autograd, nd
from incubator_mxnet_tpu.gluon.block import pure_forward
from incubator_mxnet_tpu.gluon.model_zoo import vision
from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet
from incubator_mxnet_tpu.gluon.parameter import shape_only_init

#: sha256 over the sorted [tree name, global name, shape] rows, at 0b7d489
NAME_DIGESTS = {
    "resnet18_v1": (102, "24b82a35df19279ae5ee149bf00d65f2"
                         "a3bebd16abf8d23fb3505265fe008bfd"),
    "resnet34_v1": (182, "3df0019ed5f0f534b914afef07be8ff6"
                         "aa493d47214711c0f20cf4b1e710420a"),
    "resnet50_v1": (299, "50768caa59dbbc37a06bfae8886350c8"
                         "9df6c8532a07aec101feef128a35ab5c"),
    "resnet101_v1": (588, "aadf728f5316eba25ca8dea8475c859e"
                          "0a502cbe719b7c4bdd88bfc05ef61700"),
    "resnet152_v1": (877, "0350f76a75b38b79c98f5d64fb169c7f"
                          "f94ebb769994094b8b16ac8d1d4d6b4b"),
    "resnet18_v2": (98, "dfd84a6d4130d813aff42fca2b2462be"
                        "f2b85e0dd0f6e66bda4fdb9d1f5afc48"),
    "resnet34_v2": (178, "f131e02b8f1aa660799f1acaea475423"
                         "bd3e4d4ed1744470a3e1455604762c17"),
    "resnet50_v2": (259, "c5989c9ffd561a46f2c91710562bf78b"
                         "850887ecb209a62eadd1083ab026a5dc"),
    "resnet101_v2": (514, "650ded4c558cf0da10794973e22dd8e8"
                          "a1140827956c71654091abc4cebbfa00"),
    "resnet152_v2": (769, "c9ed0c48e051940d6f9432671bb991b8"
                          "ac0ac2049d9537a8c4c8dabe8d3de565"),
}


_GLOBAL_NAME = re.compile(r"([a-z0-9]*[a-z])(\d+)_(\w+)")


def name_rows(net):
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                       jax.ShapeDtypeStruct((1, 3, 224, 224), "float32"))
    by_tree = net._collect_params_with_prefix()
    first = {}
    for p in by_tree.values():
        kind, n, _ = _GLOBAL_NAME.fullmatch(p.name).groups()
        first[kind] = min(first.get(kind, int(n)), int(n))

    def relative(name):
        kind, n, leaf = _GLOBAL_NAME.fullmatch(name).groups()
        return "%s%d_%s" % (kind, int(n) - first[kind], leaf)

    return sorted([tree, relative(p.name), list(p.shape)]
                  for tree, p in by_tree.items())


@pytest.mark.parametrize("name", sorted(NAME_DIGESTS))
def test_resnet_keeps_its_parameter_names(name):
    net = getattr(vision, name)()
    net.initialize()
    rows = name_rows(net)
    assert len(rows) == NAME_DIGESTS[name][0]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() \
        == NAME_DIGESTS[name][1]


# ---------------------------------------------------------------------------
# the residual blocks against a plain block
# ---------------------------------------------------------------------------

def _conv(x, w, b=None, stride=1, pad=0):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    return y if b is None else y + b[None, :, None, None]


class _Plain:
    """Holds a block's arrays by tree name and collects the moving
    statistics a training-mode forward leaves: BatchNorm over (N, H, W) with
    the biased variance, eps 1e-5, and moving = 0.9 moving + 0.1 batch."""

    def __init__(self, arrays):
        self.a, self.moved = arrays, {}

    def conv(self, x, at, stride=1, pad=0):
        return _conv(x, self.a[at + ".weight"], self.a.get(at + ".bias"),
                     stride, pad)

    def bn(self, x, at):
        mean, var = x.mean((0, 2, 3)), x.var((0, 2, 3))
        self.moved[at + ".running_mean"] = (
            0.9 * self.a[at + ".running_mean"] + 0.1 * mean)
        self.moved[at + ".running_var"] = (
            0.9 * self.a[at + ".running_var"] + 0.1 * var)
        c = (None, slice(None), None, None)
        return ((x - mean[c]) / jnp.sqrt(var[c] + 1e-5)
                * self.a[at + ".gamma"][c] + self.a[at + ".beta"][c])


def _basic_v1(p, x, stride, down):
    y = jax.nn.relu(p.bn(p.conv(x, "body.0", stride, 1), "body.1"))
    y = p.bn(p.conv(y, "body.3", 1, 1), "body.4")
    if down:
        x = p.bn(p.conv(x, "downsample.0", stride), "downsample.1")
    return jax.nn.relu(y + x)


def _bottleneck_v1(p, x, stride, down):
    y = jax.nn.relu(p.bn(p.conv(x, "body.0", stride), "body.1"))
    y = jax.nn.relu(p.bn(p.conv(y, "body.3", 1, 1), "body.4"))
    y = p.bn(p.conv(y, "body.6"), "body.7")
    if down:
        x = p.bn(p.conv(x, "downsample.0", stride), "downsample.1")
    return jax.nn.relu(y + x)


def _basic_v2(p, x, stride, down):
    y = jax.nn.relu(p.bn(x, "bn1"))
    if down:
        x = p.conv(y, "downsample", stride)
    y = p.conv(y, "conv1", stride, 1)
    y = p.conv(jax.nn.relu(p.bn(y, "bn2")), "conv2", 1, 1)
    return y + x


def _bottleneck_v2(p, x, stride, down):
    y = jax.nn.relu(p.bn(x, "bn1"))
    if down:
        x = p.conv(y, "downsample", stride)
    y = p.conv(y, "conv1")
    y = p.conv(jax.nn.relu(p.bn(y, "bn2")), "conv2", stride, 1)
    y = p.conv(jax.nn.relu(p.bn(y, "bn3")), "conv3")
    return y + x


_BLOCKS = {
    "BasicBlockV1": _basic_v1, "BottleneckV1": _bottleneck_v1,
    "BasicBlockV2": _basic_v2, "BottleneckV2": _bottleneck_v2,
}


@pytest.mark.parametrize("down", [False, True], ids=["identity", "downsample"])
@pytest.mark.parametrize("cls", sorted(_BLOCKS))
def test_residual_block_matches_a_plain_block(cls, down):
    """Training mode, float32: the block's output and every moving statistic
    it leaves, from the same seeded weights (norm scales and moving variances
    away from their initial ones, so that each is seen to be used)."""
    channels, stride = 16, 2 if down else 1
    in_channels = 8 if down else channels
    block = getattr(resnet, cls)(channels, stride, down,
                                 in_channels=in_channels)
    block.initialize()
    rng = np.random.RandomState(7)
    x = rng.standard_normal((4, in_channels, 8, 8)).astype(np.float32)
    block(nd.array(x))  # resolves the deferred shapes
    by_tree = block._collect_params_with_prefix()
    arrays = {}
    for tree, p in sorted(by_tree.items()):
        if tree.endswith(("gamma", "running_var")):
            value = rng.uniform(0.5, 1.5, p.shape)
        else:
            value = 0.3 * rng.standard_normal(p.shape)
        arrays[tree] = jnp.asarray(value, jnp.float32)
        p.set_data(nd.array(np.asarray(arrays[tree])))

    plain = _Plain(arrays)
    want = _BLOCKS[cls](plain, jnp.asarray(x), stride, down)
    with autograd.record():
        got = block(nd.array(x))
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    moving = sorted(t for t in by_tree if "running_" in t)
    assert moving == sorted(plain.moved) and moving
    for tree in moving:
        np.testing.assert_allclose(by_tree[tree].data().asnumpy(),
                                   np.asarray(plain.moved[tree]), rtol=1e-5,
                                   atol=1e-6, err_msg=tree)


# ---------------------------------------------------------------------------
# what left with PR 32 is refused, not ignored
# ---------------------------------------------------------------------------

def _dense_step(**kwargs):
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_train_step

    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    return make_train_step(net, gluon.loss.L2Loss(), lint="off", **kwargs)


@pytest.mark.parametrize("build,error", [
    (lambda: _dense_step(passes="maxpool_bwd_mask"),
     pytest.raises(ValueError, match="unknown graftpass")),
    (lambda: vision.resnet50_v1(ghost_bn=16), pytest.raises(TypeError)),
    (lambda: vision.resnet50_v1(s2d_stem=True), pytest.raises(TypeError)),
], ids=["maxpool_bwd_mask", "ghost_bn", "s2d_stem"])
def test_removed_names_are_refused(build, error):
    with error:
        build()
