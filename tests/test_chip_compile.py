"""The chip's compiler, asked about the kernels of the main path.

``jax.experimental.topologies`` describes a TPU v5e that is not attached,
and ``jit(f).lower(shapes).compile()`` then raises exactly what the chip's
compiler would raise: a Mosaic refusal, scoped VMEM exhausted, a program
that does not fit HBM.  Interpret mode (every other kernel test) sees none
of that.  Each case here is one kernel at its real width, about two
seconds; nothing runs, so nothing here says a kernel is right or fast:
the benchmark's cells do that on the chip.

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


def _qkv(one_chip):
    return jax.ShapeDtypeStruct((4, 16, 2048, 64), jnp.bfloat16,
                                sharding=one_chip)


def _kernels(compiled_text):
    """The names of the program's Pallas kernels, sorted."""
    return sorted(re.search(r"(flash_\w+)\)*/pallas_call", line).group(1)
                  for line in compiled_text.splitlines()
                  if "tpu_custom_call" in line)


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
    # forward (residuals), and the one backward kernel for dq, dk and dv
    assert _kernels(compiled.as_text()) == ["flash_bwd", "flash_fwd"]


def _grad(one_chip, heads, kv_heads, d, tokens=8192, **kw):
    """The gradient of causal flash attention over 8,192 tokens (or
    ``tokens``) in bfloat16, and its arguments' shapes on the described
    chip."""
    q = jax.ShapeDtypeStruct((1, heads, tokens, d), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, kv_heads, tokens, d), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True,
                                     **kw).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)


def _lowered_grad(*args, **kw):
    """``_grad``'s gradient lowered for the described chip and not yet
    compiled."""
    grad, shapes = _grad(*args, **kw)
    return jax.jit(grad).lower(*shapes)


def _compute_bodies(jaxpr):
    """For each Pallas kernel of a program's jaxpr, its conditional bodies
    that hold a matrix product, each as whether it masks (makes an iota),
    sorted."""
    def eqns(j):
        for eqn in j.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    def holds(j, name):
        return any(e.primitive.name == name for e in eqns(j))

    bodies = {}
    for call in eqns(jaxpr.jaxpr):
        if call.primitive.name != "pallas_call":
            continue
        kernel = call.params["jaxpr"]
        bodies[call.params["name"]] = sorted(
            holds(branch.jaxpr, "iota") for cond in kernel.eqns
            if cond.primitive.name == "cond"
            for branch in cond.params["branches"]
            if holds(branch.jaxpr, "dot_general"))
    return bodies


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_window_and_grouped_heads_compile_for_v5e(one_chip, mosaic,
                                                        window):
    """The decoder cell's attention at its real size: 8,192 tokens, 32 query
    heads over 4 key/value heads of 128, tiles of 1024 x 1024, with and
    without the window of 2,048; the forward kernel and the one backward
    kernel, which holds dq, dk and dv of the sequence in VMEM (12 MiB of
    float32) and asks for what that takes.  With a window or grouped heads
    the Pallas kernels are the default path."""
    text = _lowered_grad(one_chip, 32, 4, 128, window=window, block_q=1024,
                         block_k=1024).compile().as_text()
    assert _kernels(text) == ["flash_bwd", "flash_fwd"]
    # no (S, S) score array in the program
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)


@pytest.mark.parametrize("tiles", [(1024, 1024), (512, 1024), (2048, 512)],
                         ids=["its_own", "as_before_pr35", "refused"])
def test_flash_at_head_size_256_compiles_for_v5e_at_the_tiles_it_uses(
        one_chip, mosaic, tiles):
    """Latent attention's shape (``text.LatentAttention``): 8,192 tokens, 20
    ungrouped heads of 256, no window, through ``use_pallas=True`` (without
    it this shape takes XLA's attention on a chip, which holds 20 x 8,192^2
    scores).  The blocks and sums are twice those of head size 128.  The
    backward kernel asks for its VMEM, so the other decoder's 1024 x 1024
    tiles fit here too and are the family's (the dk/dv kernel it replaced
    overran the 16 MiB a kernel gets unasked with them, which held the
    family to 512 x 1024); what is refused first is the FORWARD kernel,
    which asks for nothing, at 2048 x 512."""
    from incubator_mxnet_tpu.gluon.model_zoo import text

    assert (text.LatentAttention.BLOCK_Q,
            text.LatentAttention.BLOCK_K) == (1024, 1024)
    lowered = _lowered_grad(one_chip, 20, 20, 256, block_q=tiles[0],
                            block_k=tiles[1], use_pallas=True)
    if tiles == (2048, 512):
        with pytest.raises(Exception, match="vmem.*flash_fwd"):
            lowered.compile()
        return
    compiled_text = lowered.compile().as_text()
    assert _kernels(compiled_text) == ["flash_bwd", "flash_fwd"]
    assert not re.search(r"\[(\d+,)*8192,8192\]", compiled_text)


def _asked(lowered):
    """Bytes of VMEM each Pallas kernel of a lowered program asks for."""
    return {name: int(size) for size, name in re.findall(
        r'size[^:]*: (\d+)\}\]\}", kernel_name = "(flash_\w+)"',
        lowered.as_text())}


@pytest.mark.parametrize("heads,kv_heads,d", [(32, 4, 128), (20, 20, 256)],
                         ids=["32_over_4_heads_of_128", "20_heads_of_256"])
def test_flash_backward_asks_for_its_vmem_from_the_shapes(one_chip, mosaic,
                                                          heads, kv_heads, d):
    """At both decoder cells' shapes (tiles 1024 x 1024) the one backward
    kernel asks the compiler for exactly what ``_fused_bwd_vmem`` counts
    from the shapes (the lowered call's ``scoped_memory_configs``), which
    is under the budget of three quarters of the chip's 128 MiB and enough:
    the compile passes.  The forward kernel asks for nothing."""
    lowered = _lowered_grad(one_chip, heads, kv_heads, d, block_q=1024,
                            block_k=1024, use_pallas=True)
    want = flash._fused_bwd_vmem(8192, 8192, d, 1024, 1024,
                                 heads // kv_heads, 2, d)
    assert _asked(lowered) == {"flash_bwd": want}
    # 45 and 50 MiB, where the compiler takes 28 and under 40
    assert 40 * 2 ** 20 < want < flash._vmem_budget() * 5 // 8
    assert flash._vmem_budget() == 96 * 2 ** 20
    lowered.compile()


@pytest.mark.parametrize("heads,kv_heads,d,window,tokens,fused_mib", [
    (20, 20, 256, None, 16384, 66), (20, 20, 256, None, 32768, None),
    (32, 32, 128, None, 65536, 94), (32, 32, 128, None, 131072, None),
    (32, 4, 128, None, 32768, 93), (32, 4, 128, None, 65536, None),
    (32, 4, 128, 2048, 32768, 93), (32, 4, 128, 2048, 65536, None)],
    ids=str)
def test_flash_backward_compiles_on_both_sides_of_its_vmem_budget(
        one_chip, mosaic, heads, kv_heads, d, window, tokens, fused_mib):
    """Each caller's shape (tiles 1024 x 1024) at the longest power of two
    whose sums fit the budget, where the one kernel asks for 66 to 94 MiB
    of the chip's 128 and the compiler grants it, and at the next, where dq
    and dk/dv come from the two tile kernels.  Those ask too (what the fused
    kernel would for a sequence of one tile: 31 MiB at head size 128, 36 at
    256): unasked, ``flash_bwd_dkv`` at 1024 x 1024 and head size 256 overran
    the 16 MiB a kernel gets, which is what held that family to 512 x 1024
    before PR 35."""
    mib = 2 ** 20
    lowered = _lowered_grad(one_chip, heads, kv_heads, d, tokens,
                            window=window, block_q=1024, block_k=1024,
                            use_pallas=True)
    if fused_mib:
        assert _asked(lowered) == {"flash_bwd": fused_mib * mib}
        assert fused_mib * mib <= flash._vmem_budget()
        kernels = ["flash_bwd", "flash_fwd"]
    else:
        tile = (31 if d == 128 else 36) * mib
        assert _asked(lowered) == {"flash_bwd_dq": tile,
                                   "flash_bwd_dkv": tile}
        kernels = ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    text = lowered.compile().as_text()
    assert _kernels(text) == kernels
    assert not re.search(r"\[(\d+,)*%d,%d\]" % (tokens, tokens), text)


@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_flash_at_28_over_4_heads_and_16384_tokens_compiles_for_v5e(
        one_chip, mosaic, window):
    """The third decoder cell's attention at its real size
    (``text.GroupedAttention``): 16,384 tokens, 28 query heads over 4
    key/value heads of 128 (a group of SEVEN: the kernels divide by a number
    that is no power of two, and dk, dv outlive seven query heads), tiles of
    1024 x 1024, under the window of 4,096 and without one.  The one
    backward kernel holds dq, dk and dv of the sequence in VMEM (24 MiB of
    float32) and asks for what ``_fused_bwd_vmem`` counts from the shapes:
    61 MiB of the 96 MiB budget.  Each kernel has two compute bodies, and
    both compile: the tile the mask's edge crosses pays for the mask, the
    tile wholly inside it (120 of 136 full, 42 of 70 under the window) makes
    no iota."""
    from incubator_mxnet_tpu.gluon.model_zoo import text

    assert (text.GroupedAttention.BLOCK_Q,
            text.GroupedAttention.BLOCK_K) == (1024, 1024)
    grad, shapes = _grad(one_chip, 28, 4, 128, 16384, window=window,
                         block_q=1024, block_k=1024, use_pallas=True)
    assert _compute_bodies(jax.make_jaxpr(grad)(*shapes)) == {
        "flash_fwd": [False, True], "flash_bwd": [False, True]}
    lowered = jax.jit(grad).lower(*shapes)
    want = flash._fused_bwd_vmem(16384, 16384, 128, 1024, 1024, 7, 2, 128)
    assert _asked(lowered) == {"flash_bwd": want}
    assert 60 * 2 ** 20 < want < 62 * 2 ** 20 < flash._vmem_budget()
    compiled_text = lowered.compile().as_text()
    assert _kernels(compiled_text) == ["flash_bwd", "flash_fwd"]
    assert not re.search(r"\[(\d+,)*16384,16384\]", compiled_text)


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


@pytest.mark.parametrize("tokens,top_k,d,held", [
    (8192, 8, 2048, 16), (8192, 4, 2048, 8), (16384, 6, 2560, 8)],
    ids=["8_of_128_at_2048", "4_of_64_at_2048", "6_of_64_at_2560"])
def test_dispatch_and_combine_are_row_movers_that_stop_at_n(
        one_chip, mosaic, tokens, top_k, d, held):
    """Dispatch and combine at each decoder cell's shapes (8,192 tokens of
    hidden 2,048 under 8 and 4 choices; 16,384 tokens of hidden 2,560 under
    6: 98,304 rows that travel as slabs of 4,096 values; bf16), forward and
    backward: the pass back to tokens and both transposes are Pallas kernels
    (``moe_slabs``, ``moe_rows``, ``moe_tokens``: a row travels as a slab of
    32-bit words, ``parallel/moe_rows.py``), the one gather left is the rows
    into expert order, no float32 array of the row buffer's size is left,
    the dynamic extent stays inside the kernels (no ``while`` or
    ``conditional`` in the program), and only the SLAB is wider than its
    row: no array of rows or of tokens is padded to the slab's width for a
    kernel or cut back after it, so at 2,560 the program holds no array of
    4,096 columns and no ``pad`` of an array of rows or of tokens."""
    from incubator_mxnet_tpu.parallel import moe

    rows_ = tokens * top_k

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(x, ys, weights, sel):
        rows, sizes, row, order = moe.moe_dispatch(x, sel,
                                                   experts_held=(0, held))
        out = moe.moe_combine(ys + rows, weights, sizes, row, order)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape(tokens, d), shape(rows_, d),
        shape(tokens, top_k, dtype=jnp.float32),
        shape(tokens, top_k, dtype=jnp.int32)).compile().as_text()
    kernels = [re.search(r"(moe_[a-z]+)/pallas_call", line).group(1)
               for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(kernels) == ["moe_rows"] + ["moe_slabs"] * 3 + [
        "moe_tokens"] * 2, kernels
    assert re.findall(r"(\w+\[[\d,]+\])\S* gather\(", text) == [
        "bf16[%d,%d]" % (rows_, d)]
    assert not re.search(r" (while|conditional)\(", text)
    assert not re.search(r"f32\[(%d,%d|%d,%d,%d)\]"
                         % (rows_, d, tokens, top_k, d), text)
    slab = -(-d // 2048) * 2048
    if slab != d:
        assert not re.search(r"\[(%d|%d),%d\]" % (rows_, tokens, slab), text)
        assert not re.search(r"\[(%d|%d),\d+\]\S* pad\(" % (rows_, tokens),
                             text)


@pytest.mark.parametrize("rows_,d,f,held,act", [
    (98304, 2560, 768, 8, "relu"), (65536, 2048, 1024, 16, "silu"),
    (32768, 2048, 1536, 8, "silu")],
    ids=["98304x768_relu", "65536x1024_silu", "32768x1536_silu"])
def test_the_expert_ops_elementwise_passes_are_kernels_that_stop_at_n(
        one_chip, mosaic, rows_, d, f, held, act):
    """The expert op and its backward at each decoder cell's shapes (bf16):
    the gate, its transpose (ReLU or SiLU, ``jax.vjp`` of the activation
    inside the kernel) and the sum of the two row cotangents are the Pallas
    kernels ``moe_gate``, ``moe_gate_bwd`` and ``moe_row_sum``
    (``moe_rows.on_held_rows``), the nine products are the compiler's
    grouped kernels (the forward products that ``jax.vjp`` traces beside
    their transposes are dead and gone), and nothing else makes an array of
    the buffer's rows: no fusion, no ``pad``, no slice, no loop."""
    from incubator_mxnet_tpu.parallel import moe

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both(rows, w1, w3, w2, sizes, cot):
        ys, pull = jax.vjp(
            lambda *o: moe.moe_experts(*o, sizes, act=act), rows, w1, w3, w2)
        return ys, pull(cot)

    text = jax.jit(both).lower(
        shape(rows_, d), shape(held, d, f), shape(held, d, f),
        shape(held, f, d), shape(held, dtype=jnp.int32),
        shape(rows_, d)).compile().as_text()
    kernels = re.findall(r'op_name="[^"]*/(moe_[a-z_]+)/pallas_call"', text)
    assert sorted(kernels) == ["moe_gate", "moe_gate_bwd", "moe_row_sum"]
    assert len(re.findall(r'op_name="ragged-dot-none"', text)) == 9
    assert not re.search(
        r"= \w+\[%d,\d+\]\S* (fusion|pad|slice|add|multiply|copy)\(" % rows_,
        text)
    assert not re.search(r" (while|conditional)\(", text)


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
        (256, 64, 112, 112), jnp.bfloat16, sharding=one_chip)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" select-and-scatter\(", text)) == 1
    assert not re.search(r" pad\(", text)


@pytest.mark.parametrize("shape", [
    (1, 28, 16384, 128), (1, 4, 16384, 128), (1, 32, 8192, 128),
    (1, 4, 8192, 128), (1, 20, 8192, 64), (1, 1, 8192, 64)],
    ids=["28x16384x128", "4x16384x128", "32x8192x128", "4x8192x128",
         "20x8192x64", "1x8192x64"])
def test_rotary_is_one_kernel_each_way(one_chip, mosaic, shape):
    """The rotary op at each decoder cell's q and k (bf16): at head size 128
    the forward and the backward are each ONE Pallas kernel (``rotary``,
    ``rotary_bwd``), and no float32 array of the heads or of half of them
    is left, where XLA's form wrote the ``-x2`` half and both products out
    as float32; at 64 (``glm47_flash_train_8k``'s rope columns and its
    shared key) XLA's form stays, faster there alone (PERF.md section 6,
    PR 41)."""
    from incubator_mxnet_tpu.ops import decoder

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    heads, s, d = shape[1:]
    for fn, name in (
            (lambda x, g: decoder._rotary(x, 1e4), "rotary"),
            (lambda x, g: jax.vjp(lambda x: decoder._rotary(x, 1e4), x)[1](
                g)[0], "rotary_bwd")):
        text = jax.jit(fn).lower(x, x).compile().as_text()
        kernels = re.findall(r'op_name="[^"]*/(rotary\w*)/pallas_call"', text)
        if d % 128:
            assert "tpu_custom_call" not in text
            continue
        assert kernels == [name], kernels
        assert text.count("tpu_custom_call") == 1
        # the (S, D) tables are the only float32 arrays
        assert not re.search(r"f32\[(\d+,)*%d,%d\]" % (s, d // 2), text)
        assert not re.search(r"f32\[(\d+,)*%d,\d+,\d+\]" % heads, text)


def test_flash_at_192_over_128_heads_and_16384_tokens_compiles_for_v5e(
        one_chip, mosaic):
    """The latent attention of ``kimi_linear_train_16k``: 32 heads of 128 +
    64 query/key columns (no rotary) and 128 value columns, 16,384 tokens,
    tiles of 1024 x 1024 (``LatentAttention.BLOCK_Q/K``).  The kernels take
    the value width as its own: v, the output, do and dv are 128 wide, q,
    k, dq and dk 192; the one backward kernel asks for what
    ``_fused_bwd_vmem`` counts with the two widths."""
    from incubator_mxnet_tpu.gluon.model_zoo import text

    assert (text.LatentAttention.BLOCK_Q,
            text.LatentAttention.BLOCK_K) == (1024, 1024)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 192), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5, block_q=1024,
            block_k=1024, use_pallas=True).astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    shapes = jax.eval_shape(grad, q, q, v)
    assert [s.shape[-1] for s in shapes] == [192, 192, 128]
    lowered = jax.jit(grad).lower(q, q, v)
    want = flash._fused_bwd_vmem(16384, 16384, 192, 1024, 1024, 1, 2, 128)
    assert _asked(lowered) == {"flash_bwd": want}
    assert want < flash._vmem_budget()
    compiled_text = lowered.compile().as_text()
    assert _kernels(compiled_text) == ["flash_bwd", "flash_fwd"]


def test_kda_kernels_compile_for_v5e_at_the_cells_shapes(one_chip, mosaic):
    """The KDA recurrence of ``kimi_linear_train_16k``: 16,384 tokens of 32
    heads of 128 (q, k, v in bf16, g and beta in float32), forward and
    backward: ONE kernel each way, whose chunk mathematics (the sub-chunk
    triangles, the doubling inverse, the float32 products at the highest
    precision) Mosaic takes; the state each chunk starts from is the one
    float32 array of (heads, chunks, 128, 128) the forward pass leaves."""
    from incubator_mxnet_tpu.parallel import delta_rule

    x = jax.ShapeDtypeStruct((1, 16384, 4096), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 16384, 4096), jnp.float32,
                             sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 16384, 32), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v, g, beta):
        return delta_rule.kda(q, k, v, g, beta).astype(jnp.float32).sum()

    def kernels(text):
        return text.count("tpu_custom_call"), sorted(set(re.findall(
            r'op_name="[^"]*/(kda_\w+)/pallas_call"', text)))

    fwd = jax.jit(delta_rule.kda).lower(x, x, x, g, beta).compile()
    assert kernels(fwd.as_text()) == (1, ["kda_fwd"])
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile().as_text()
    assert kernels(grad) == (2, ["kda_bwd", "kda_fwd"])
    assert re.search(r"f32\[1,32,256,128,128\]", grad)
