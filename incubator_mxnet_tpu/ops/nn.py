"""Neural-network operators: conv, pooling, norm, dense, dropout, softmax-loss.

Parity: ``src/operator/nn/*`` (Convolution convolution.cc:399, BatchNorm
batch_norm.cc:493, Pooling pooling.cc:365, FullyConnected
fully_connected.cc:258, softmax.cc, dropout, LayerNorm/GroupNorm/InstanceNorm,
LRN, Activation, UpSampling) plus ``softmax_output.cc`` and ``leaky_relu``.

TPU-native: every op is a pure jnp/lax function that XLA lowers onto the
MXU (convs/matmuls) and fuses elementwise tails into.  There is no cuDNN-style
wrapper layer: `lax.conv_general_dilated` / `reduce_window` ARE the fused
kernels.  Layouts follow the reference's NCHW default for API parity; XLA
re-layouts internally for the TPU's native tiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import OPS, register


def _is_train():
    from .. import autograd, tracing

    tc = tracing.current_trace()
    if tc is not None:
        return tc.training
    return autograd.is_training()


# ---------------------------------------------------------------------------
# FullyConnected (fully_connected.cc:258-348)
# ---------------------------------------------------------------------------


@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    if flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    # weight: (num_hidden, input_dim) — reference layout
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (convolution.cc, deconvolution.cc)
# ---------------------------------------------------------------------------


def _conv_dims(ndim, layout):
    """Build lax dimension_numbers for NC* layouts (1/2/3 spatial dims)."""
    if layout is None or layout.startswith("NC"):
        lhs = "NC" + "DHW"[3 - (ndim - 2):]
        return (lhs, "OI" + "DHW"[3 - (ndim - 2):], lhs)
    if layout in ("NWC", "NHWC", "NDHWC"):
        spatial = layout[1:-1]
        return (layout, "O" + spatial + "I", layout)
    raise ValueError("unsupported conv layout %r" % layout)


@register("Convolution", aliases=("conv",))
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, workspace=1024,
                 no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    nspatial = data.ndim - 2
    stride = tuple(stride) if stride else (1,) * nspatial
    dilate = tuple(dilate) if dilate else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dims(data.ndim, layout))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if bias is not None and not no_bias:
        if layout in ("NWC", "NHWC", "NDHWC"):
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * nspatial)
    return out


@register("Deconvolution", aliases=("deconv",))
def _deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                   pad=None, adj=None, target_shape=None, num_filter=None,
                   num_group=1, workspace=512, no_bias=True, cudnn_tune=None,
                   cudnn_off=False, layout=None):
    nspatial = data.ndim - 2
    stride = tuple(stride) if stride else (1,) * nspatial
    dilate = tuple(dilate) if dilate else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    # weight layout (in_channels, out_channels/group, *kernel) — reference
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dims(data.ndim, layout))
    out = lax.conv_general_dilated(
        data, jnp.flip(weight, axis=tuple(range(2, weight.ndim))).swapaxes(0, 1)
        if num_group == 1 else weight,
        window_strides=(1,) * nspatial,
        padding=[(d * (k - 1) - p, d * (k - 1) - p + a)
                 for k, p, d, a in zip(weight.shape[2:], pad, dilate,
                                       tuple(adj) if adj else (0,) * nspatial)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nspatial)
    return out


# ---------------------------------------------------------------------------
# Pooling (pooling.cc:365)
# ---------------------------------------------------------------------------


@register("Pooling", aliases=("pool",))
def _pooling(data, kernel=None, pool_type="max", global_pool=False,
             cudnn_off=False, pooling_convention="valid", stride=None, pad=None,
             p_value=2, count_include_pad=True, layout=None):
    nspatial = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            red = jnp.sum if pool_type == "sum" else jnp.mean
            return red(data, axis=axes, keepdims=True)
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value), axis=axes,
                                     keepdims=True), 1.0 / p_value)
    kernel = tuple(kernel)
    stride = tuple(stride) if stride else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode output: pad high edge enough for ceil division
        padding = [(0, 0), (0, 0)] + [
            (p, p + s - 1) for p, s in zip(pad, stride)
        ]
    else:
        padding = [(0, 0), (0, 0)] + [(p, p) for p in pad]

    # NB: scalar init values keep the reduce recognizable as the max/add
    # monoid so XLA uses the dedicated (differentiable) pooling primitives.
    if pool_type == "max":
        # init must carry the operand dtype (an int-typed pool — e.g. the
        # int8 inference path — rejects a python-int/int64 init)
        init = (-jnp.inf if jnp.issubdtype(data.dtype, jnp.floating)
                else np.asarray(jnp.iinfo(data.dtype).min, data.dtype)[()])
        # The backward is reduce_window's own transpose, ONE
        # select-and-scatter a pool, dx written once: 1.48 ms for
        # ResNet-50's stem pool and 3.97 ms for VGG-16's five on the v5e,
        # where a backward of masks and pads took 15.9 and 23.5 ms
        # (PERF.md section 6, PR 28).
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0 if jnp.issubdtype(
            data.dtype, jnp.floating) else 0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            return summed / np.prod(kernel)
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        powed = lax.reduce_window(jnp.power(jnp.abs(data), p_value), 0.0,
                                  lax.add, window, strides, padding)
        return jnp.power(powed, 1.0 / p_value)
    raise ValueError("unknown pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# Normalization (batch_norm.cc:493, layer_norm.cc, group_norm.cc, ...)
# ---------------------------------------------------------------------------


@register("BatchNorm", aliases=("batch_norm",))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False):
    """Normalize over all axes except ``axis``.

    Training (and not use_global_stats): batch statistics; otherwise moving
    stats.  Running-stat *updates* are the caller's job (gluon layer /
    executor aux-write) — this fn is pure.
    """
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    use_batch = _is_train() and not use_global_stats
    if use_batch:
        # single-pass stats (E[x], E[x^2] in one read of the activation —
        # jnp.var would re-read it for the deviation pass); f32 accumulation
        # keeps bf16 inputs well-conditioned
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red_axes)
        var = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=red_axes) - jnp.square(mean), 0.0)
    else:
        mean, var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = (g.astype(jnp.float32) * inv).reshape(bshape)
    shift = (beta.astype(jnp.float32) - mean.astype(jnp.float32) * g.astype(jnp.float32) * inv).reshape(bshape)
    out = (data.astype(jnp.float32) * scale + shift).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("batch_norm_stats", num_inputs=1, differentiable=False)
def _batch_norm_stats(data, axis=1):
    """Helper (not in reference): batch mean/var for running-stat updates."""
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    x = data.astype(jnp.float32)
    mean = jnp.mean(x, axis=red_axes)
    # same single-pass form as the BatchNorm body so whole-graph CSE folds
    # the two computations into one reduction
    var = jnp.maximum(
        jnp.mean(jnp.square(x), axis=red_axes) - jnp.square(mean), 0.0)
    return mean, var


def _batch_norm_aux_update(in_vals, out_vals, momentum=0.9, axis=1,
                           use_global_stats=False, **_):
    """Running-stat update for BatchNorm's mutated inputs (moving_mean=3,
    moving_var=4) — the single source of the momentum math shared by the
    gluon layer, TrainStep and the symbolic Executor
    (``src/operator/nn/batch_norm.cc`` stateful forward)."""
    if use_global_stats and str(use_global_stats).lower() != "false":
        return {}
    mean, var = _batch_norm_stats(in_vals[0], axis=int(axis))
    m = float(momentum)
    old_m, old_v = in_vals[3], in_vals[4]
    return {3: (m * old_m.astype(jnp.float32)
                + (1 - m) * mean).astype(old_m.dtype),
            4: (m * old_v.astype(jnp.float32)
                + (1 - m) * var).astype(old_v.dtype)}


OPS["BatchNorm"].aux_update = _batch_norm_aux_update
OPS["BatchNorm"].mutate_idx = (3, 4)


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data.astype(jnp.float32), axis=axis, keepdims=True)
    var = jnp.var(data.astype(jnp.float32), axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    norm = (data.astype(jnp.float32) - mean) * inv
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = (norm * gamma.astype(jnp.float32).reshape(bshape)
           + beta.astype(jnp.float32).reshape(bshape)).astype(data.dtype)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(inv, axis)
    return out


@register("GroupNorm", aliases=("group_norm",))
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5, output_mean_var=False):
    n, c = data.shape[:2]
    g = int(num_groups)
    x = data.reshape((n, g, c // g) + data.shape[2:]).astype(jnp.float32)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    norm = ((x - mean) * lax.rsqrt(var + eps)).reshape(data.shape)
    bshape = (1, c) + (1,) * (data.ndim - 2)
    out = (norm * gamma.astype(jnp.float32).reshape(bshape)
           + beta.astype(jnp.float32).reshape(bshape)).astype(data.dtype)
    if output_mean_var:
        return out, mean.reshape(n, g), var.reshape(n, g)
    return out


@register("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    x = data.astype(jnp.float32)
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    norm = (x - mean) * lax.rsqrt(var + eps)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return (norm * gamma.reshape(bshape) + beta.reshape(bshape)).astype(data.dtype)


@register("L2Normalization", aliases=("l2_normalization",), num_inputs=1)
def _l2_normalization(data, eps=1e-10, mode="instance"):
    x = data.astype(jnp.float32)
    if mode == "instance":
        norm = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
        norm = norm.reshape((-1,) + (1,) * (data.ndim - 1))
    elif mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True))
    elif mode == "spatial":
        norm = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], x.shape[1], -1)),
                                axis=2)).reshape(x.shape[:2] + (1,) * (data.ndim - 2))
    else:
        raise ValueError(mode)
    return (x / (norm + eps)).astype(data.dtype)


@register("LRN", aliases=("lrn",), num_inputs=1)
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    x = data.astype(jnp.float32)
    sq = jnp.square(x)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    win = sum(padded[:, i:i + x.shape[1]] for i in range(nsize))
    return (x / jnp.power(knorm + alpha * win / nsize, beta)).astype(data.dtype)


# ---------------------------------------------------------------------------
# Activations (activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------


@register("Activation", num_inputs=1)
def _activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU", needs_rng=True)
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334, key=None):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 and data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        if _is_train():
            s = jax.random.uniform(key, data.shape, jnp.float32, lower_bound, upper_bound)
            return jnp.where(data >= 0, data, s.astype(data.dtype) * data)
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise ValueError("unknown act_type %r" % act_type)


@register("SoftmaxActivation", num_inputs=1, aliases=("softmax_activation",))
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# Dropout (dropout.cc)
# ---------------------------------------------------------------------------


@register("Dropout", num_inputs=1, needs_rng=True)
def _dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False, key=None):
    if p <= 0 or (mode != "always" and not _is_train()):
        return data
    keep = 1.0 - p
    shape = list(data.shape)
    for a in axes or ():
        shape[a] = 1
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype))


# ---------------------------------------------------------------------------
# SoftmaxOutput (softmax_output.cc:155) — custom gradient: d = (p - onehot(y))
# ---------------------------------------------------------------------------


@register("SoftmaxOutput", num_inputs=2, aliases=("Softmax", "softmax_output"))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    @jax.custom_vjp
    def f(d, l):
        return _softmax_fwd(d)

    def _softmax_fwd(d):
        if multi_output:
            return jax.nn.softmax(d, axis=1)
        if preserve_shape:
            return jax.nn.softmax(d, axis=-1)
        return jax.nn.softmax(d.reshape(d.shape[0], -1), axis=-1).reshape(d.shape)

    def fwd(d, l):
        out = _softmax_fwd(d)
        return out, (out, l)

    def bwd(res, g):
        out, l = res
        if multi_output:
            # out: (n, c, ...) label: (n, ...)
            oh = jax.nn.one_hot(l.astype(jnp.int32), out.shape[1], dtype=out.dtype,
                                axis=1)
            grad = out - oh
            if use_ignore:
                mask = (l != ignore_label).astype(out.dtype)
                grad = grad * jnp.expand_dims(mask, 1)
        elif preserve_shape:
            # out (..., C), label (...): per-position softmax grad
            k = out.shape[-1]
            oh = jax.nn.one_hot(l.astype(jnp.int32), k, dtype=out.dtype)
            if smooth_alpha:
                oh = oh * (1.0 - smooth_alpha) + smooth_alpha / (k - 1) * (1.0 - oh)
            grad = out - oh
            if use_ignore:
                mask = (l != ignore_label).astype(out.dtype)
                grad = grad * mask[..., None]
        else:
            flat = out.reshape(out.shape[0], -1)
            oh = jax.nn.one_hot(l.reshape(-1).astype(jnp.int32), flat.shape[-1],
                                dtype=out.dtype)
            if smooth_alpha:
                k = flat.shape[-1]
                oh = oh * (1.0 - smooth_alpha) + smooth_alpha / (k - 1) * (1.0 - oh)
            grad = (flat - oh).reshape(out.shape)
            if use_ignore:
                mask = (l.reshape(-1) != ignore_label).astype(out.dtype)
                grad = grad * mask.reshape((-1,) + (1,) * (grad.ndim - 1))
        scale = grad_scale
        if normalization == "batch":
            scale = scale / out.shape[0]
        elif normalization == "valid" and use_ignore:
            valid = jnp.maximum(jnp.sum((l != ignore_label).astype(out.dtype)), 1.0)
            scale = scale / valid
        grad = grad * scale
        if out_grad:
            grad = grad * g
        return grad, jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


# ---------------------------------------------------------------------------
# Resizing (upsampling.cc, contrib bilinear_resize)
# ---------------------------------------------------------------------------


@register("UpSampling", needs_rng=False)
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
                multi_input_mode="concat", workspace=512):
    data = args[0]
    if sample_type == "nearest":
        outs = []
        for a in args:
            s = scale
            o = jnp.repeat(jnp.repeat(a, s, axis=2), s, axis=3)
            outs.append(o)
        if len(outs) == 1:
            return outs[0]
        if multi_input_mode == "sum":
            return sum(outs)
        return jnp.concatenate(outs, axis=1)
    # bilinear: args = (data, weight) — implement as resize (weight unused
    # in the common initialization case)
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register("_contrib_BilinearResize2D", num_inputs=1, aliases=("BilinearResize2D",))
def _bilinear_resize(data, height=None, width=None, scale_height=None,
                     scale_width=None, mode="size", align_corners=True):
    n, c, h, w = data.shape
    oh = int(height) if height else int(round(h * scale_height))
    ow = int(width) if width else int(round(w * scale_width))
    return jax.image.resize(data, (n, c, oh, ow), method="bilinear")


@register("_contrib_AdaptiveAvgPooling2D", num_inputs=1)
def _adaptive_avg_pool(data, output_size=(1, 1)):
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    n, c, h, w = data.shape
    oh, ow = output_size
    x = data.reshape(n, c, oh, h // oh, ow, w // ow)
    return x.mean(axis=(3, 5))


# ---------------------------------------------------------------------------
# CTC loss (nn/ctc_loss.cc)
# ---------------------------------------------------------------------------


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss"))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    import optax

    # data: (seq, batch, alphabet) -> optax wants (batch, seq, alphabet)
    logits = jnp.swapaxes(data, 0, 1)
    b, t, k = logits.shape
    labels = label.astype(jnp.int32)
    if blank_label == "first":
        # optax uses blank=0 by default; mxnet 'first' means blank==0 and
        # labels are 1-based already
        pass
    else:
        labels = labels + 1  # shift so blank can sit at 0
    logit_pad = jnp.zeros((b, t))
    if use_data_lengths and data_lengths is not None:
        steps = jnp.arange(t)[None, :]
        logit_pad = (steps >= data_lengths.astype(jnp.int32)[:, None]).astype(jnp.float32)
    lab_pad = (labels <= 0).astype(jnp.float32)
    if use_label_lengths and label_lengths is not None:
        steps = jnp.arange(labels.shape[1])[None, :]
        lab_pad = (steps >= label_lengths.astype(jnp.int32)[:, None]).astype(jnp.float32)
    return optax.ctc_loss(logits, logit_pad, labels, lab_pad, blank_id=0)
