"""The chip's compiler, asked about the kernels of the main path.

``jax.experimental.topologies`` describes a TPU v5e that is not attached,
and ``jit(f).lower(shapes).compile()`` then raises exactly what the chip's
compiler would raise: a Mosaic refusal, scoped VMEM exhausted, a program
that does not fit HBM.  Interpret mode (every other kernel test) sees none
of that.  Each case here is one kernel at its real ResNet-50 batch-256
width, about two seconds; nothing runs, so nothing here says a kernel is
right or fast — ``chip_smoke.py`` does that on the chip.

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be, never at import, in a ``skipif`` or in ``parametrize``
arguments; this is the only file that does it, because the worker that
loads the TPU library keeps its lock until it exits; and the interpret
helper is patched here, by the test, since ``jax.default_backend()`` is
``cpu`` in this process.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu import _backend
from incubator_mxnet_tpu.parallel import fused_bn

flash = importlib.import_module("incubator_mxnet_tpu.parallel.flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower through Mosaic, not the interpreter; and the compiles
    stay out of the persistent cache (an executable for a described chip
    cannot be read back without one, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(_backend, "pallas_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# Every distinct BN site a batch-256 bf16 ghost_bn=16 ResNet-50 v1 traces
# (test_sites_are_what_resnet50_traces pins the list to the model):
# (C, HW, has_res, donate, dual) -> (fwd plan, bwd plan, tpu_custom_calls
# in value_and_grad).  "jnp" is a direction that is NOT on Pallas, by plan,
# from shapes; what the v5e compiler said to the form it replaced is beside
# it.  Custom calls: whole-L fused/lanefold = 1 per direction, two-phase
# tiled = 2.
R50_SITES = [
    # stem.  The lane-fold fwd compiles since its fold-reduce rotates lanes
    # instead of reshaping them ("infer-vector-layout: unsupported shape
    # cast", tpu.reshape vector<128xf32> -> vector<2x64xf32>).  Its bwd is
    # 3 windows x 2 x 25.7 MB even folded: over VMEM, so jnp.
    ((64, 112, False, False, False), ("lanefold", "jnp", 1)),
    ((64, 56, False, False, False), ("fused", "fused", 2)),
    ((256, 56, False, False, False), ("fused", "tiled", 3)),
    # 56x56x256 exits: "RESOURCE_EXHAUSTED: ... Used 148.31M of 128.00M
    # vmem" for the whole-L fwd the old plan chose under donate (3 windows
    # of 49 MiB: aliasing saves no window); "Scoped allocation with size
    # 122.50M and limit 120.00M" for its half-L dual bwd (5 windows)
    ((256, 56, True, True, True), ("tiled", "tiled", 4)),
    ((256, 56, True, False, True), ("tiled", "tiled", 4)),
    ((512, 28, False, False, False), ("fused", "fused", 2)),
    ((128, 28, False, False, False), ("fused", "fused", 2)),
    # 28x28x512 exits: "Scoped allocation with size 122.50M and limit
    # 120.00M exceeded" for the whole-L residual bwd (5 windows of 24.5 MiB)
    ((512, 28, True, True, True), ("fused", "tiled", 3)),
    ((512, 28, True, False, True), ("fused", "tiled", 3)),
    ((1024, 14, False, False, False), ("fused", "fused", 2)),
    ((256, 14, False, False, False), ("fused", "fused", 2)),
    ((1024, 14, True, True, True), ("fused", "fused", 2)),
    ((1024, 14, True, False, True), ("fused", "fused", 2)),
    ((2048, 7, False, False, False), ("fused", "fused", 2)),
    ((512, 7, False, False, False), ("fused", "fused", 2)),
    ((2048, 7, True, True, True), ("fused", "fused", 2)),
    ((2048, 7, True, False, True), ("fused", "fused", 2)),
    ((2048, 7, True, False, False), ("fused", "fused", 2)),
]
_N, _GROUP = 256, 16


def _site_id(site):
    (c, hw, res, donate, dual), _ = site
    return "%dx%d%s%s%s" % (c, hw, "_res" if res else "",
                            "_don" if donate else "", "_dual" if dual else "")


@pytest.mark.parametrize("site", R50_SITES, ids=_site_id)
def test_resnet50_bn_site_compiles_for_v5e(site, one_chip, mosaic):
    (c, hw, has_res, donate, dual), (fwd, bwd, n_calls) = site
    shape = (_N, c, hw, hw)
    # (donate changes the program — Y aliases the residual — not the plan)
    d = fused_bn.plan_describe(*shape, 2, _GROUP, has_res, dual)
    assert (d["variant"], d["bwd"]) == (fwd, bwd), d

    def loss(x, gamma, beta, res):
        out = fused_bn.ghost_bn_act(x, gamma, beta, res, 1e-5, "relu",
                                    _GROUP, donate_residual=donate,
                                    dual_out=dual)
        total = out[0].astype(jnp.float32).sum()
        if dual:
            total = total + 2.0 * out[1].astype(jnp.float32).sum()
        return total

    def sds(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    args = (sds(shape, jnp.bfloat16), sds((c,), jnp.float32),
            sds((c,), jnp.float32),
            sds(shape, jnp.bfloat16) if has_res else None)
    argnums = (0, 1, 2, 3) if has_res else (0, 1, 2)
    compiled = jax.jit(jax.value_and_grad(loss, argnums)).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == n_calls


def test_sites_are_what_resnet50_traces():
    """The table above is the model's, not a guess: an abstract trace of
    the ghost_bn=16 step ``bench.py --ghost-bn 16`` builds, at batch 256
    (zero compiles, no chip), goes through exactly these sites — the ones
    ``chip_smoke.train(..., ghost_bn=16)`` records on the chip."""
    import bench

    _, step = bench.build_train_step(image_size=224, ghost_bn=_GROUP,
                                     passes=(), cost="off")
    with fused_bn.record_sites() as sites:
        step.analyze_cost(
            jax.ShapeDtypeStruct((_N, 3, 224, 224), jnp.float32),
            jax.ShapeDtypeStruct((_N,), jnp.float32))
    assert all(s[0][0] == _N and s[1] == "bfloat16" and s[2] == _GROUP
               for s in sites), sites
    traced = {(s[0][1], s[0][2], s[3], s[4], s[5]) for s in sites}
    assert traced == {site for site, _ in R50_SITES}


def _qkv(one_chip):
    return jax.ShapeDtypeStruct((4, 16, 2048, 64), jnp.bfloat16,
                                sharding=one_chip)


def test_flash_attention_fwd_compiles_for_v5e(one_chip, mosaic):
    q = _qkv(one_chip)
    compiled = jax.jit(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, use_pallas=True)).lower(q, q, q).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, mosaic):
    q = _qkv(one_chip)

    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=True, use_pallas=True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    # forward (residuals), dq, and dk/dv kernels
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_window_and_grouped_heads_compile_for_v5e(one_chip, mosaic,
                                                        window):
    """The decoder cell's attention at its real size: 8,192 tokens, 32 query
    heads over 4 key/value heads of 128, tiles of 1024 x 1024, with and
    without the window of 2,048; forward, dq and dk/dv kernels.  With a
    window or grouped heads the Pallas kernels are the default path."""
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=True, window=window, block_q=1024,
            block_k=1024).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    # no (S, S) score array in the program
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)


def test_grouped_expert_product_is_the_compilers_own_kernel(one_chip, mosaic):
    """``lax.ragged_dot`` over the held experts at the published widths
    becomes XLA's grouped-product kernel (a custom call it names
    ``ragged-dot-none``, which the benchmark's metrics read by that name),
    not a dense product over every expert."""
    from incubator_mxnet_tpu.parallel import moe

    rows = jax.ShapeDtypeStruct((65536, 2048), jnp.bfloat16,
                                sharding=one_chip)
    w13 = jax.ShapeDtypeStruct((16, 2048, 1024), jnp.bfloat16,
                               sharding=one_chip)
    w2 = jax.ShapeDtypeStruct((16, 1024, 2048), jnp.bfloat16,
                              sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    text = jax.jit(moe.moe_experts).lower(
        rows, w13, w13, w2, sizes).compile().as_text()
    assert len(re.findall(r'op_name="ragged-dot-none"', text)) == 3
    assert not re.search(r" (dot|convolution)\(", text)


def test_stem_maxpool_has_no_pallas_form(one_chip, mosaic):
    """The stem max-pool (256,64,112,112) window 3x3 stride 2 is on jnp by
    construction: the argmax-carrying Pallas forward never compiled — W
    rides the lanes of an NCHW block and the v5e compiler refused every
    stride along it ("'vector.extract_strided_slice' op expected strides to
    be confined to [1, 2)"; "Strided load with non 32-bit data"; "Stride on
    last dim is not 1") — so the kernel is gone.  ``op.Pooling`` is
    reduce_window and its own transpose, and the chip's compiler makes of
    the backward ONE select-and-scatter (1.48 ms a step on the chip, PERF.md
    section 6, PR 28): no custom call, and no pad of the pool input's shape
    per in-window offset, which is what the shifted-window unpool that stood
    here cost 15.9 ms with."""
    from incubator_mxnet_tpu.ops import nn as opsnn

    def loss(x):
        return opsnn._pooling(jnp.maximum(x, 0), kernel=(3, 3), stride=(2, 2),
                              pad=(1, 1)).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).lower(jax.ShapeDtypeStruct(
        (_N, 64, 112, 112), jnp.bfloat16, sharding=one_chip)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" select-and-scatter\(", text)) == 1
    assert not re.search(r" pad\(", text)
