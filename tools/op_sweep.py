"""Reflection-driven operator case synthesis.

For every distinct op in the registry, synthesize a concrete call
(input arrays + attrs) that the op accepts, using its ``op_info``
signature plus a curated hint table for shape-constrained families
(conv/pool/rnn/indexing/...).  Consumers:

* ``tests/test_op_sweep.py`` — CPU forward sweep vs ``op.infer``
  metadata + numeric-gradient checks on differentiable ops (the
  reference's ``check_numeric_gradient``-everywhere strategy,
  tests/python/unittest/test_operator.py).
* ``tools/check_consistency.py`` — TPU-vs-CPU forward battery over the
  same cases (the reference's cross-device consistency harness,
  python/mxnet/test_utils.py:1422).

``build_cases()`` returns ``{op_name: (arrays, attrs) or None}`` —
None means no generic candidate fit and no hint exists (reported, so
coverage is measurable, never silently truncated).
"""
from __future__ import annotations

import sys

import numpy as np

_RNG = np.random.RandomState(0)


def _f(*shape):
    return (_RNG.uniform(0.3, 1.7, shape)).astype(np.float32)


def _fn(*shape):
    return _RNG.normal(0.0, 1.0, shape).astype(np.float32)


def _idx(hi, *shape):
    # int32: index-like inputs must not be float, or the numeric-gradient
    # sweep would perturb them across integer boundaries
    return _RNG.randint(0, hi, shape).astype(np.int32)


# --------------------------------------------------------------------------
# curated hints: op -> (arrays, attrs); lazily evaluated so np draws are
# deterministic per build_cases() call
# --------------------------------------------------------------------------

def _hints():
    B, C, H, W = 2, 4, 8, 8
    x4 = _fn(B, C, H, W)
    T, N, I, S = 5, 2, 3, 4  # rnn: time, batch, input, state
    h = {
        # --- nn core ---
        "Convolution": ([_fn(B, C, H, W), _fn(8, C, 3, 3), _fn(8)],
                        {"kernel": (3, 3), "num_filter": 8, "pad": (1, 1)}),
        "Deconvolution": ([_fn(B, C, H, W), _fn(C, 8, 3, 3), _fn(8)],
                          {"kernel": (3, 3), "num_filter": 8}),
        "Pooling": ([x4], {"kernel": (2, 2), "stride": (2, 2),
                           "pool_type": "max"}),
        "Pooling_v1": ([x4], {"kernel": (2, 2), "stride": (2, 2),
                              "pool_type": "avg"}),
        "FullyConnected": ([_fn(B, 6), _fn(5, 6), _fn(5)],
                           {"num_hidden": 5}),
        "BatchNorm": ([x4, _f(C), _fn(C), _fn(C), _f(C)], {}),
        "BatchNorm_v1": ([x4, _f(C), _fn(C), _fn(C), _f(C)], {}),
        "_contrib_SyncBatchNorm": ([x4, _f(C), _fn(C), _fn(C), _f(C)],
                                   {"key": "sweep"}),
        "LayerNorm": ([_fn(B, 6), _f(6), _fn(6)], {}),
        "GroupNorm": ([x4, _f(C), _fn(C)], {"num_groups": 2}),
        "InstanceNorm": ([x4, _f(C), _fn(C)], {}),
        "L2Normalization": ([x4], {}),
        "LRN": ([x4], {"nsize": 3}),
        "SoftmaxActivation": ([_fn(B, 6)], {}),
        "SoftmaxOutput": ([_fn(B, 6), _idx(6, B)], {}),
        "Softmax": ([_fn(B, 6), _idx(6, B)], {}),
        "softmax": ([_fn(B, 6)], {}),
        "log_softmax": ([_fn(B, 6)], {}),
        "softmin": ([_fn(B, 6)], {}),
        "masked_softmax": ([_fn(B, 6),
                            (_RNG.rand(B, 6) > 0.3)], {}),
        "masked_log_softmax": ([_fn(B, 6),
                                (_RNG.rand(B, 6) > 0.3)], {}),
        "Activation": ([x4], {"act_type": "relu"}),
        "LeakyReLU": ([x4], {}),
        "PReLU": ([x4, _f(1)], {"act_type": "prelu"}),
        "Dropout": ([x4], {"key": "sweep"}),
        "CTCLoss": ([_fn(T, B, 6), _idx(5, B, 3) + 1], {}),
        "Correlation": ([x4, _fn(B, C, H, W)], {"kernel_size": 1,
                                                "max_displacement": 2,
                                                "stride1": 1, "stride2": 1}),
        "SpatialTransformer": (
            [x4, _fn(B, 6)],
            {"target_shape": (8, 8), "transform_type": "affine",
             "sampler_type": "bilinear"}),
        "GridGenerator": ([_fn(B, 6)], {"transform_type": "affine",
                                        "target_shape": (8, 8)}),
        "BilinearSampler": ([x4, _RNG.uniform(-1, 1, (B, 2, H, W))
                             .astype(np.float32)], {}),
        "ROIPooling": ([x4, np.array([[0, 0, 0, 4, 4]], np.float32)],
                       {"pooled_size": (2, 2), "spatial_scale": 1.0}),
        "_contrib_ROIAlign": ([x4, np.array([[0, 0, 0, 4, 4]], np.float32)],
                              {"pooled_size": (2, 2), "spatial_scale": 1.0}),
        "UpSampling": ([x4], {"scale": 2, "sample_type": "nearest"}),
        "Pad": ([x4], {"mode": "constant",
                       "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)}),
        "pad": ([x4], {"mode": "constant",
                       "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)}),
        "Embedding": ([_idx(10, B, 3), _fn(10, 5)],
                      {"input_dim": 10, "output_dim": 5}),
        "take": ([_fn(6, 4), _idx(6, B, 2)], {}),
        "batch_take": ([_fn(B, 4), _idx(4, B)], {}),
        "gather_nd": ([_fn(4, 5), _idx(4, 2, 3)], {}),
        "scatter_nd": ([_fn(2, 3), _idx(4, 1, 2)], {"shape": (4, 3)}),
        "_backward_gather_nd": ([_fn(2, 3), _idx(4, 1, 2)],
                                {"shape": (4, 3)}),
        "_scatter_set_nd": ([_fn(4, 3), _fn(2, 3), _idx(4, 1, 2)],
                           {"shape": (4, 3)}),
        "one_hot": ([_idx(5, B, 3)], {"depth": 5}),
        "pick": ([_fn(B, 5), _idx(5, B)], {}),
        "where": ([(_RNG.rand(3, 4) > 0.5), _fn(3, 4), _fn(3, 4)], {}),
        "SequenceMask": ([_fn(T, B, 3), _f(B) + 1], {
            "use_sequence_length": True}),
        "SequenceLast": ([_fn(T, B, 3), _f(B) + 1], {
            "use_sequence_length": True}),
        "SequenceReverse": ([_fn(T, B, 3), _f(B) + 1], {
            "use_sequence_length": True}),
        "RNN": ([_fn(T, N, I), _fn((I + S + 2) * S), _fn(1, N, S)],
                {"state_size": S, "num_layers": 1, "mode": "rnn_tanh",
                 "key": "sweep"}),
        "SliceChannel": ([_fn(B, 4, 3)], {"num_outputs": 2, "axis": 1}),
        "split_v2": ([_fn(B, 4, 3)], {"indices": (2,), "axis": 1}),
        "Concat": ([_fn(B, 3), _fn(B, 3)], {"dim": 1, "num_args": 2}),
        "stack": ([_fn(B, 3), _fn(B, 3)], {"num_args": 2}),
        "add_n": ([_fn(B, 3), _fn(B, 3)], {}),
        "Custom": None,        # needs a registered python CustomOp
        "_CustomFunction": None,
        # --- losses / misc ---
        "MakeLoss": ([_f(B, 3)], {}),
        "smooth_l1": ([_fn(B, 3)], {}),
        "LinearRegressionOutput": ([_fn(B, 3), _fn(B, 3)], {}),
        "MAERegressionOutput": ([_fn(B, 3), _fn(B, 3)], {}),
        "LogisticRegressionOutput": ([_fn(B, 3), _f(B, 3)], {}),
        "SVMOutput": ([_fn(B, 5), _idx(5, B)], {}),
        "IdentityAttachKLSparseReg": ([_f(B, 3)], {}),
        "BlockGrad": ([_fn(B, 3)], {}),
        "CrossDeviceCopy": ([_fn(B, 3)], {}),
        "_identity_with_attr_like_rhs": ([_fn(B, 3), _fn(B, 3)], {}),
        "softmax_cross_entropy": ([_fn(B, 5), _idx(5, B)], {}),
        # --- tensor manipulation needing attrs ---
        "Reshape": ([_fn(B, 6)], {"shape": (3, 4)}),
        "reshape_like": ([_fn(2, 6), _fn(3, 4)], {}),
        "transpose": ([_fn(2, 3, 4)], {}),
        "expand_dims": ([_fn(2, 3)], {"axis": 1}),
        "slice": ([_fn(4, 5)], {"begin": (1, 0), "end": (3, 4)}),
        "slice_axis": ([_fn(4, 5)], {"axis": 0, "begin": 1, "end": 3}),
        "slice_like": ([_fn(4, 5), _fn(2, 3)], {}),
        "_slice_assign": ([_fn(4, 5), _fn(2, 5)],
                          {"begin": (1,), "end": (3,)}),
        "_slice_assign_scalar": ([_fn(4, 5)],
                                 {"begin": (1,), "end": (3,),
                                  "scalar": 1.5}),
        "clip": ([_fn(3, 4)], {"a_min": -0.5, "a_max": 0.5}),
        "repeat": ([_fn(2, 3)], {"repeats": 2}),
        "tile": ([_fn(2, 3)], {"reps": (2, 1)}),
        "reverse": ([_fn(3, 4)], {"axis": 0}),
        "flip": ([_fn(3, 4)], {"axis": 0}),
        "roll": ([_fn(3, 4)], {"shift": 1}),
        "rot90": ([_fn(3, 4)], {}),
        "depth_to_space": ([_fn(B, 8, 2, 2)], {"block_size": 2}),
        "space_to_depth": ([_fn(B, 2, 4, 4)], {"block_size": 2}),
        "swapaxes": ([_fn(2, 3, 4)], {"dim1": 0, "dim2": 2}),
        "Flatten": ([_fn(2, 3, 4)], {}),
        "Cast": ([_fn(2, 3)], {"dtype": "float64"}),
        "amp_cast": ([_fn(2, 3)], {"dtype": "float32"}),
        "amp_multicast": ([_fn(2, 3), _fn(2, 3)], {"num_outputs": 2}),
        "Crop": ([_fn(B, C, 8, 8)], {"h_w": (4, 4), "num_args": 1}),
        "crop": ([_fn(B, C, 8, 8)], {"h_w": (4, 4), "num_args": 1}),
        "pad_v2": None,
        "squeeze": ([_fn(2, 1, 3)], {}),
        "broadcast_to": ([_fn(1, 3)], {"shape": (4, 3)}),
        "broadcast_like": ([_fn(1, 3), _fn(4, 3)], {}),
        "broadcast_axis": ([_fn(1, 3)], {"axis": 0, "size": 4}),
        "cast_storage": ([_fn(3, 4)], {"stype": "default"}),
        # indexing / sorting
        "argsort": ([_fn(3, 4)], {}),
        "topk": ([_fn(3, 6)], {"k": 2}),
        "sort": ([_fn(3, 4)], {}),
        "argmax": ([_fn(3, 4)], {}),
        "argmin": ([_fn(3, 4)], {}),
        "argmax_channel": ([_fn(3, 4)], {}),
        "Dot": ([_fn(3, 4), _fn(4, 5)], {}),
        "dot": ([_fn(3, 4), _fn(4, 5)], {}),
        "batch_dot": ([_fn(B, 3, 4), _fn(B, 4, 5)], {}),
        "diag": ([_fn(4, 4)], {}),
        "norm": ([_fn(3, 4)], {}),
        "IdentityWithLoss": None,
        # --- init-like ops (shape attrs) ---
        "_zeros": ([], {"shape": (2, 3)}),
        "_ones": ([], {"shape": (2, 3)}),
        "_full": ([], {"shape": (2, 3), "value": 1.5}),
        "_eye": ([], {"N": 3}),
        "_arange": ([], {"start": 0, "stop": 6}),
        "_linspace": ([], {"start": 0, "stop": 1, "num": 5}),
        "_zeros_without_dtype": ([], {"shape": (2, 3)}),
        "zeros_like": ([_fn(2, 3)], {}),
        "ones_like": ([_fn(2, 3)], {}),
        "shape_array": ([_fn(2, 3)], {}),
        "size_array": ([_fn(2, 3)], {}),
        # --- long-tail hints (ops the generic candidates can't satisfy) ---
        "_contrib_BilinearResize2D": ([x4], {"height": 4, "width": 4}),
        "_contrib_DeformableConvolution": (
            [_fn(B, C, H, W), _fn(2 * 3 * 3, H, W) * 0 + _fn(B, 2 * 9, H, W),
             _fn(8, C, 3, 3), _fn(8)][0:1]
            + [_fn(B, 2 * 9, H, W), _fn(8, C, 3, 3), _fn(8)],
            {"kernel": (3, 3), "num_filter": 8, "pad": (1, 1)}),
        "_contrib_DeformablePSROIPooling": (
            [_fn(B, 8, H, W), np.array([[0, 0, 0, 4, 4]], np.float32),
             _fn(1, 2 * 2 * 2, 2, 2)],
            {"spatial_scale": 1.0, "output_dim": 2, "group_size": 2,
             "pooled_size": 2, "part_size": 2, "sample_per_part": 2,
             "trans_std": 0.1}),
        "_contrib_MultiBoxDetection": (
            [_f(1, 8, 2), _fn(1, 8 * 4), _RNG.uniform(0.1, 0.4, (1, 8, 4))
             .astype(np.float32)], {}),
        "_contrib_MultiBoxTarget": (
            [_RNG.uniform(0.1, 0.4, (1, 8, 4)).astype(np.float32),
             np.array([[[0, 0.1, 0.1, 0.4, 0.4]]], np.float32),
             _fn(1, 2, 8)], {}),
        "_contrib_Proposal": (
            [_f(1, 2 * 3, 4, 4), _fn(1, 4 * 3, 4, 4),
             np.array([[16, 16, 1.0]], np.float32)],
            {"feature_stride": 4, "scales": (8,), "ratios": (0.5, 1, 2),
             "rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
             "rpn_min_size": 1}),
        "_contrib_boolean_mask": ([_fn(4, 3),
                                   np.array([1, 0, 1, 1], np.float32)], {}),
        "_contrib_box_encode": (
            [np.ones((1, 4), np.float32), _idx(4, 1, 4),
             _RNG.uniform(0.1, 0.4, (1, 4, 4)).astype(np.float32),
             _RNG.uniform(0.1, 0.4, (1, 4, 4)).astype(np.float32)], {}),
        "_contrib_calibrate_entropy": (
            [np.maximum(_RNG.poisson(5, 64), 0).astype(np.float32),
             np.linspace(-4, 4, 65).astype(np.float32)], {}),
        "_contrib_hawkesll": (
            [_f(3), _f(3) * 0.3, _f(3), _RNG.exponential(1, (2, 5))
             .astype(np.float32), _idx(3, 2, 5),
             np.full(2, 5, np.float32), np.full(2, 6.0, np.float32)], {}),
        "_contrib_interleaved_matmul_selfatt_qk": (
            [_fn(T, B, 3 * 2 * 4)], {"heads": 2}),
        "_contrib_interleaved_matmul_selfatt_valatt": (
            [_fn(T, B, 3 * 2 * 4), _f(B * 2, T, T)], {"heads": 2}),
        "_contrib_interleaved_matmul_encdec_qk": (
            [_fn(T, B, 2 * 4), _fn(T, B, 2 * 2 * 4)], {"heads": 2}),
        "_contrib_interleaved_matmul_encdec_valatt": (
            [_fn(T, B, 2 * 2 * 4), _f(B * 2, T, T)], {"heads": 2}),
        "_contrib_quantized_conv": (
            [(_RNG.randint(-100, 100, (B, C, H, W))).astype(np.int8),
             (_RNG.randint(-100, 100, (8, C, 3, 3))).astype(np.int8),
             (_RNG.randint(-100, 100, (8,))).astype(np.int8),
             np.float32(-1), np.float32(1), np.float32(-1), np.float32(1),
             np.float32(-1), np.float32(1)],
            {"kernel": (3, 3), "num_filter": 8, "pad": (1, 1)}),
        "_contrib_quantized_fully_connected": (
            [(_RNG.randint(-100, 100, (B, 6))).astype(np.int8),
             (_RNG.randint(-100, 100, (5, 6))).astype(np.int8),
             (_RNG.randint(-100, 100, (5,))).astype(np.int8),
             np.float32(-1), np.float32(1), np.float32(-1), np.float32(1),
             np.float32(-1), np.float32(1)],
            {"num_hidden": 5}),
        "_image_resize": ([(_RNG.rand(8, 8, 3) * 255).astype(np.uint8)],
                          {"size": (4, 4)}),
        "_linalg_maketrian": ([_fn(1, 6)], {}),
        "_np_moveaxis": ([_fn(2, 3, 4)], {"source": 0, "destination": 2}),
        "_np_roll": ([_fn(3, 4)], {"shift": 1}),
        "_np_unique": ([_idx(5, 12)], {}),
        "_npi_bincount": ([_idx(6, 10).astype(np.int32)], {}),
        "_npi_bitwise_not": ([_idx(6, 3, 4).astype(np.int32)], {}),
        "_npi_bitwise_or": ([_idx(6, 3, 4).astype(np.int32),
                             _idx(6, 3, 4).astype(np.int32)], {}),
        "_npi_bitwise_or_scalar": ([_idx(6, 3, 4).astype(np.int32)],
                                   {"scalar": 3}),
        "_npi_bitwise_xor": ([_idx(6, 3, 4).astype(np.int32),
                              _idx(6, 3, 4).astype(np.int32)], {}),
        "_npi_bitwise_xor_scalar": ([_idx(6, 3, 4).astype(np.int32)],
                                    {"scalar": 3}),
        "_npi_choice": ([], {"a": 10, "size": (4,), "key": "sweep"}),
        "_npi_delete": ([_fn(5, 3)], {"obj": 1, "axis": 0}),
        "_npi_einsum": ([_fn(3, 4), _fn(4, 5)],
                        {"subscripts": "ij,jk->ik"}),
        "_npi_lcm": ([_idx(6, 3).astype(np.int32) + 1,
                      _idx(6, 3).astype(np.int32) + 1], {}),
        "_npi_lcm_scalar": ([_idx(6, 3).astype(np.int32) + 1],
                            {"scalar": 4}),
        "_npi_svd": ([_fn(4, 3)], {}),
        "_npi_tensorinv": ([(_fn(6, 6) + np.eye(6, dtype=np.float32) * 4)
                            .reshape(2, 3, 2, 3)], {"ind": 2}),
        "_npi_tensorsolve": ([_fn(3, 3) + np.eye(3, dtype=np.float32) * 3,
                              _fn(3)], {}),
        "_ravel_multi_index": ([_idx(3, 2, 4)], {"shape": (4, 4)}),
        "_sample_unique_zipfian": ([], {"range_max": 20, "shape": (1, 5)}),
        "_unravel_index": ([_idx(12, 4)], {"shape": (4, 4)}),
        "col2im": ([_fn(B, C * 4, 16)],
                   {"output_size": (8, 8), "kernel": (2, 2),
                    "stride": (2, 2)}),
        "im2col": ([x4], {"kernel": (2, 2), "stride": (2, 2)}),
        "multi_sgd_update": ([_fn(3, 4), _fn(3, 4), _fn(2, 3), _fn(2, 3)],
                             {"lrs": (0.1, 0.1), "wds": (0.0, 0.0),
                              "num_weights": 2}),
        "multi_sgd_mom_update": (
            [_fn(3, 4), _fn(3, 4), _fn(3, 4), _fn(2, 3), _fn(2, 3),
             _fn(2, 3)],
            {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2}),
        "multi_mp_sgd_update": (
            [_fn(3, 4), _fn(3, 4), _fn(3, 4).astype(np.float32),
             _fn(2, 3), _fn(2, 3), _fn(2, 3)],
            {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2}),
        "multi_mp_sgd_mom_update": (
            [_fn(3, 4), _fn(3, 4), _fn(3, 4), _fn(3, 4),
             _fn(2, 3), _fn(2, 3), _fn(2, 3), _fn(2, 3)],
            {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2}),
        # domain-restricted elementwise ops
        "arcsin": ([_RNG.uniform(-0.9, 0.9, (3, 4)).astype(np.float32)], {}),
        "arccos": ([_RNG.uniform(-0.9, 0.9, (3, 4)).astype(np.float32)], {}),
        "arctanh": ([_RNG.uniform(-0.9, 0.9, (3, 4)).astype(np.float32)],
                    {}),
        "erfinv": ([_RNG.uniform(-0.9, 0.9, (3, 4)).astype(np.float32)], {}),
        "arccosh": ([_RNG.uniform(1.1, 3.0, (3, 4)).astype(np.float32)], {}),
        "_npi_arcsin": ([_RNG.uniform(-0.9, 0.9, (3, 4))
                         .astype(np.float32)], {}),
        "_npi_arccos": ([_RNG.uniform(-0.9, 0.9, (3, 4))
                         .astype(np.float32)], {}),
        "_npi_arctanh": ([_RNG.uniform(-0.9, 0.9, (3, 4))
                          .astype(np.float32)], {}),
        "_npi_arccosh": ([_RNG.uniform(1.1, 3.0, (3, 4))
                          .astype(np.float32)], {}),
        # optimizer updates with positivity-constrained state
        "rmspropalex_update": ([_fn(3, 4), _fn(3, 4), _f(3, 4) + 1,
                                np.zeros((3, 4), np.float32),
                                np.zeros((3, 4), np.float32)], {"lr": 0.1}),
        "rmsprop_update": ([_fn(3, 4), _fn(3, 4), _f(3, 4)], {"lr": 0.1}),
        # square / SPD linalg inputs
        "_linalg_extracttrian": ([_fn(4, 4)], {}),
        "_linalg_potrf": ([(lambda m: (m @ m.T
                                       + 4 * np.eye(4)).astype(np.float32))
                           (_fn(4, 4))], {}),
        # control flow + Custom take python-function/registered-op attrs —
        # covered by tests/test_control_flow.py and tests/test_custom_op.py
        "_cond": None,
        "_foreach": None,
        "_while_loop": None,
    }
    h.update(_decoder_hints())
    h.update(_kda_hints())
    return h


def _decoder_hints():
    """The decoder family's ops, drawn from a stream of their own so that
    the draws of every other case stay what they were.  The expert layer
    (parallel/moe.py): 6 tokens, 2 of 4 experts a token, experts 1-2 held,
    rows sorted by expert."""
    rng = np.random.RandomState(30)

    def fn(*shape):
        return rng.normal(0.0, 1.0, shape).astype(np.float32)

    order = rng.permutation(12)
    return {
        "_contrib_moe_router": ([fn(6, 5), fn(4, 5), fn(4) * 0.01],
                                {"top_k": 2, "route_scale": 2.0}),
        "_contrib_moe_dispatch": (
            [fn(6, 5), np.stack([rng.permutation(4)[:2]
                                 for _ in range(6)]).astype(np.int32)],
            {"experts_held": (1, 2)}),
        "_contrib_moe_experts": (
            [fn(12, 5), fn(2, 5, 3), fn(2, 5, 3), fn(2, 3, 5),
             np.array([4, 3], np.int32)], {}),
        "_contrib_moe_combine": (
            [fn(12, 5), rng.uniform(0.3, 1.7, (6, 2)).astype(np.float32),
             np.array([4, 3], np.int32),
             np.argsort(order).reshape(6, 2).astype(np.int32),
             order.astype(np.int32)], {}),
        "_contrib_rms_norm": ([fn(3, 8), fn(8) + 2.0], {"eps": 1e-5}),
        "_contrib_rotary": ([fn(2, 6, 8)], {"theta": 100.0}),
    }


def _kda_hints():
    """Kimi Delta Attention's recurrence and the ops around it
    (parallel/delta_rule.py), from a stream of their own: 2 heads of 4
    over 10 tokens (a chunk padded), decays and write strengths in their
    ranges."""
    rng = np.random.RandomState(43)

    def fn(*shape):
        return rng.normal(0.0, 1.0, shape).astype(np.float32)

    def uniform(lo, hi, *shape):
        return rng.uniform(lo, hi, shape).astype(np.float32)

    return {
        "_contrib_kda": ([fn(1, 10, 8) * 0.4, fn(1, 10, 8) * 0.4,
                          fn(1, 10, 8), -uniform(0.05, 1.0, 1, 10, 8),
                          uniform(0.1, 0.9, 1, 10, 2)], {}),
        "_contrib_kda_conv": ([fn(2, 6, 4), fn(4, 4) * 0.5], {}),
        "_contrib_kda_qk_norm": ([fn(2, 6, 8)], {"heads": 2, "scale": 0.5}),
        "_contrib_kda_gate": ([fn(2, 6, 8), fn(2, 6, 2), fn(1, 1, 2, 1),
                               fn(8) * 0.5], {}),
        "_contrib_kda_out_norm": ([fn(2, 6, 8), fn(2, 6, 8), fn(4) + 2.0],
                                  {"eps": 1e-5}),
    }


# generic candidates tried in order when no hint exists
def _candidates(n_inputs):
    outs = []
    if n_inputs == 0:
        outs.append(([], {"shape": (2, 3)}))
        outs.append(([], {}))
    shapes2 = [(3, 4)] * max(n_inputs, 1)
    outs.append(([_f(*s) for s in shapes2], {}))
    outs.append(([_fn(*s) for s in shapes2], {}))
    outs.append(([_f(3, 4, 5)[0] if False else _f(4,)
                  for _ in range(max(n_inputs, 1))], {}))
    outs.append(([_f(2, 3, 4, 4) for _ in range(max(n_inputs, 1))], {}))
    return outs


def build_cases(verbose=False):
    """Synthesize one concrete call per distinct registered op.

    Returns (cases, uncovered): cases maps op name -> (arrays, attrs);
    uncovered is the list of op names with no working synthesis.
    """
    from incubator_mxnet_tpu.ops import registry

    hints = _hints()
    seen = {}
    for name, op in registry.OPS.items():
        seen.setdefault(id(op), op)
    cases, uncovered = {}, []
    for op in seen.values():
        name = op.name
        if name in hints:
            if hints[name] is None:
                uncovered.append(name)
                continue
            cases[name] = hints[name]
            continue
        n = op.num_inputs if op.num_inputs is not None else 2
        got = None
        for arrays, attrs in _candidates(n):
            try:
                import jax

                avals = [jax.ShapeDtypeStruct(np.asarray(a).shape,
                                              np.asarray(a).dtype)
                         for a in arrays]
                if op.needs_rng:
                    attrs = dict(attrs)
                    attrs["key"] = jax.random.PRNGKey(0)
                op.infer(avals, **{k: v for k, v in attrs.items()})
                got = (arrays, attrs)
                break
            except Exception as e:  # noqa: BLE001 - synthesis probing
                if verbose:
                    print("  %s: %s" % (name, e), file=sys.stderr)
        if got is not None:
            cases[name] = got
        else:
            uncovered.append(name)
    return cases, sorted(uncovered)


if __name__ == "__main__":
    sys.path.insert(0, "/root/repo")
    cases, uncovered = build_cases(verbose="-v" in sys.argv)
    print("covered: %d  uncovered: %d" % (len(cases), len(uncovered)))
    for n in uncovered:
        print("  MISSING", n)
