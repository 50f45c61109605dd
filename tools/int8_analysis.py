#!/usr/bin/env python
"""INT8 quantization coverage + ceiling analysis (asked for in the round-3 review).

Quantizes ResNet-50 (the graded int8 config) and accounts, node by node
over the quantized symbol with inferred shapes:

* what fraction of the model's FLOPs execute as int8 MXU ops,
* how many bytes the quantize/dequantize boundaries add,
* the resulting roofline prediction for int8-vs-fp32 speedup on v5e —
  i.e. whether the measured 1.76x is the kernel's fault or the
  boundary traffic's.

Run:  JAX_PLATFORMS=cpu python tools/int8_analysis.py
"""
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BATCH = 128
V5E_BF16 = 197e12
V5E_INT8 = 394e12
V5E_HBM = 819e9


def conv_flops(attrs, in_shape, out_shape):
    k = eval(attrs.get("kernel", "(1, 1)")) if isinstance(
        attrs.get("kernel"), str) else attrs.get("kernel", (1, 1))
    cin = in_shape[1]
    n, cout, h, w = out_shape
    groups = int(attrs.get("num_group", 1))
    return 2 * n * cout * h * w * cin // groups * int(np.prod(k))


def fc_flops(in_shape, out_shape):
    return 2 * int(np.prod(in_shape)) * out_shape[-1]


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.contrib.quantization import quantize_model
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.symbol.symbol import _toposort

    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, 224, 224))
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "r50")
        net.export(prefix)
        sym, args, aux = mx.model.load_checkpoint(prefix, 0)

    from incubator_mxnet_tpu.contrib.quantization import fold_batch_norm

    sym, args, aux = fold_batch_norm(sym, args, aux)
    qsym, qargs, qaux = quantize_model(sym, args, aux, calib_mode="none")

    from incubator_mxnet_tpu.symbol.symbol import _entry_key, _infer_graph

    known = {"data": (BATCH, 3, 224, 224)}
    for d in (qargs, qaux):
        for k, v in d.items():
            known[k] = tuple(v.shape)
    entry_shapes, _ = _infer_graph(qsym, known, {})

    int8_flops = 0
    f32_flops = 0
    boundary_bytes = 0
    n_boundary = {}
    per_node = []
    act_sizes = []

    def eshape(node, i=0):
        return entry_shapes.get(_entry_key(node, i))

    for node in _toposort([n for n, _ in qsym._outputs]):
        if node.is_var:
            continue
        out_shape = eshape(node)
        if out_shape is None:
            continue
        if node.op in ("_contrib_quantized_conv", "Convolution"):
            in_shape = eshape(*node.inputs[0])
            act_sizes.append(int(np.prod(out_shape)))
            fl = conv_flops(node.attrs, in_shape, out_shape)
            if node.op.startswith("_contrib_quantized"):
                int8_flops += fl
            else:
                f32_flops += fl
            per_node.append((node.name, node.op, fl))
        elif node.op in ("_contrib_quantized_fully_connected",
                         "FullyConnected"):
            in_shape = eshape(*node.inputs[0])
            fl = fc_flops(in_shape, out_shape)
            if node.op.startswith("_contrib_quantized"):
                int8_flops += fl
            else:
                f32_flops += fl
            per_node.append((node.name, node.op, fl))
        elif node.op in ("_contrib_quantize_v2", "_contrib_dequantize",
                         "_contrib_requantize"):
            # boundary op traffic per element: quantize f32r+i8w = 5,
            # dequantize i32r+f32w = 8, requantize i32r+i8w = 5
            elems = int(np.prod(out_shape))
            width = {"_contrib_quantize_v2": 5, "_contrib_dequantize": 8,
                     "_contrib_requantize": 5}[node.op]
            boundary_bytes += elems * width
            n_boundary[node.op] = n_boundary.get(node.op, 0) + 1

    total = int8_flops + f32_flops
    print("== int8 coverage (ResNet-50, batch %d) ==" % BATCH)
    print("conv/fc FLOPs as int8 : %.3e  (%.1f%%)"
          % (int8_flops, 100 * int8_flops / total))
    print("conv/fc FLOPs as f32  : %.3e  (%.1f%%)" % (f32_flops,
                                                      100 * f32_flops / total))
    print("boundary bytes/step   : %.3e (%.1f MB)" % (boundary_bytes,
                                                      boundary_bytes / 1e6))
    print("boundary node counts  : %s" % n_boundary)

    t_int8 = int8_flops / V5E_INT8
    t_f32_resid = f32_flops / V5E_BF16
    t_boundary = boundary_bytes / V5E_HBM
    t_bf16 = total / V5E_BF16
    print("\n== roofline prediction ==")
    print("bf16 all compute        : %.3f ms" % (1e3 * t_bf16))
    print("int8 mxu compute        : %.3f ms" % (1e3 * t_int8))
    print("UNFUSED boundary bound  : +%.3f ms (%.1f GB standalone "
          "requantize/quantize passes)" % (1e3 * t_boundary,
                                           boundary_bytes / 1e9))
    # with XLA fusion the requantize / quantized-add epilogues fold into
    # the conv output (the int32 accumulator never round-trips HBM): the
    # remaining activation traffic is the int8 tensors themselves
    act_elems = sum(fl_shape for fl_shape in act_sizes)
    t_act_int8 = act_elems * 1 / V5E_HBM
    t_act_bf16 = act_elems * 2 / V5E_HBM
    print("FUSED activation traffic: int8 %.3f ms vs bf16 %.3f ms"
          % (1e3 * t_act_int8, 1e3 * t_act_bf16))
    fused_int8 = max(t_int8, t_act_int8)
    fused_bf16 = max(t_bf16, t_act_bf16)
    print("fused ceiling (max of compute/BW roofs): int8 %.3f ms, "
          "bf16 %.3f ms -> %.2fx int8-over-bf16"
          % (1e3 * fused_int8, 1e3 * fused_bf16, fused_bf16 / fused_int8))
    print("unfused floor: %.2fx -> the measured speedup shows how much "
          "of the boundary XLA actually fused"
          % (t_bf16 / (t_int8 + t_f32_resid + t_boundary)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
