"""The expert op's elementwise passes follow the held rows
(``parallel/moe_rows.py::on_held_rows``; ``parallel/moe.py::moe_experts`` and
its backward): the gate, its transpose and the sum of the two row cotangents
against the plain ``jax.numpy`` forms on the rows below ``n`` at every edge
of ``n``, what lies past ``n``'s block left unwritten (the interpreter
fills it with NaN), the whole op's gradient against ``jax.grad`` of the
four-line formula, an ``ExpertFFN`` whose buffers hold NaN past the last
held row against the same block over the plain op, and the benchmark's three
controls of the op.  Pallas runs in interpret mode here; about a minute."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon.block import pure_forward
from incubator_mxnet_tpu.gluon.model_zoo import text
from incubator_mxnet_tpu.ops import registry
from incubator_mxnet_tpu.parallel import moe, moe_rows
from perfbench.references import glm47_flash, smallthinker_21b

#: three blocks of the pass or more at every width here (512 rows a block,
#: fewer where 512 of every operand and result overrun the pass's VMEM)
_R = {16: 1536, 768: 1536, 1024: 1536, 2560: 768}
#: n by name, in blocks of the pass
_N = {"none": 0.0, "one": None, "inside": 1.37, "edge": 1.0, "all": 3.0}
_TOL = {jnp.float32: 1e-6, jnp.bfloat16: 2.0 ** -7}


def _block(width, dtype, arrays):
    return moe_rows._held_block(_R[width], moe_rows._up(width, 128), arrays,
                                jnp.dtype(dtype).itemsize)


def _n(name, block):
    return 1 if name == "one" else int(_N[name] * block)


def _arrays(count, width, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(size=(_R[width], width)), dtype)
            for _ in range(count)]


def _plain(body, *arrays):
    """``body`` over whole arrays in float32, rounded once."""
    out = body(*(x.astype(jnp.float32) for x in arrays))
    return [o.astype(arrays[0].dtype) for o in out]


def _check(got, want, n, block, tol):
    """The rows below ``n`` are the plain form's; from the end of ``n``'s
    block on nothing was written."""
    written = -(-n // block) * block
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = (np.asarray(x, np.float32) for x in (g, w))
        np.testing.assert_allclose(g[:n], w[:n], rtol=tol, atol=tol)
        assert np.isnan(g[written:]).all()


def _gate(act):
    return lambda a, b: moe._GATES[act][0](a) * b


@pytest.mark.parametrize("n", sorted(_N))
@pytest.mark.parametrize("width", [16, 768, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act", sorted(moe._GATES))
def test_the_gate_over_the_held_rows_is_the_plain_form(act, dtype, width, n):
    a, b = _arrays(2, width, dtype)
    block = _block(width, dtype, 3)
    n = _n(n, block)
    got = moe._GATES[act][1](a, b, n)
    want = _plain(lambda a, b: (_gate(act)(a, b),), a, b)
    _check([got], want, n, block, _TOL[dtype])


@pytest.mark.parametrize("n", sorted(_N))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act", sorted(moe._GATES))
def test_the_gates_backward_is_one_pass_with_jaxs_own_derivative(act, dtype,
                                                                 n):
    """``(g * b * act'(a), g * act(a))`` with ``act'`` as ``jax.vjp`` of the
    activation gives it (a ReLU's is 0 at 0: the first column holds zeros)."""
    a, b, g = _arrays(3, 768, dtype, seed=1)
    a = a.at[:, 0].set(0)
    block = _block(768, dtype, 5)
    n = _n(n, block)
    got = moe._GATES[act][2](a, b, g, n)
    want = _plain(lambda a, b, g: jax.vjp(_gate(act), a, b)[1](g), a, b, g)
    _check(got, want, n, block, _TOL[dtype])
    if act == "relu" and n:
        assert not np.asarray(got[0], np.float32)[:n, 0].any()


@pytest.mark.parametrize("n", sorted(_N))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_sum_of_the_two_row_cotangents_stops_at_n(dtype, n):
    x, y = _arrays(2, 2560, dtype, seed=2)
    block = _block(2560, dtype, 3)
    assert block == {2: 256, 4: 128}[jnp.dtype(dtype).itemsize]
    n = _n(n, block)
    _check([moe._sum_rows(x, y, n)], _plain(lambda x, y: (x + y,), x, y), n,
           block, _TOL[dtype])


def test_a_pass_takes_one_to_three_arrays_and_gives_what_its_body_gives():
    x, y, z = _arrays(3, 16, jnp.float32, seed=3)
    got = moe_rows.on_held_rows(lambda x: 2 * x, 1, 700, x, name="t")
    _check(got, [2 * x], 700, 512, 1e-6)
    got = moe_rows.on_held_rows(lambda x, y, z: (x * y + z, x - z), 2, 512, x,
                                y, z, name="t")
    _check(got, [x * y + z, x - z], 512, 512, 1e-6)


def test_a_width_whose_blocks_overrun_the_passs_vmem_is_refused_by_name():
    """Five float32 arrays of 8,192 values a row leave a block 38 rows, one
    of 16,384 values 19, fewer than the 32 the body is given at a time."""
    assert moe_rows._held_block(4096, 8192, 5, 4) == 32
    with pytest.raises(ValueError, match="on_held_rows: 5 arrays of width"):
        moe_rows._held_block(4096, 16384, 5, 4)


# ---------------------------------------------------------------------------
# the op and its backward
# ---------------------------------------------------------------------------

def _plain_experts(rows, w1, w3, w2, sizes, act="silu"):
    """The four lines the op was."""
    def dot(x, w):
        return jax.lax.ragged_dot(x, w, group_sizes=sizes)

    h = moe._GATES[act][0](dot(rows, w1)) * dot(rows, w3)
    return dot(h, w2)


def _operands(dtype, r=1280, d=16, f=24, seed=4):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(size=shape) * scale, dtype)
            for shape, scale in (((r, d), 1.0), ((3, d, f), 0.3),
                                 ((3, d, f), 0.3), ((3, f, d), 0.3))]


@pytest.mark.parametrize("sizes", [(0, 0, 0), (1, 0, 0), (100, 0, 177),
                                   (256, 256, 0), (400, 480, 400)],
                         ids=["none", "one", "inside", "edge", "all"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("act", sorted(moe._GATES))
def test_the_ops_gradient_is_jax_grad_of_the_four_line_formula(act, dtype,
                                                               sizes):
    """Output and the gradients of rows, w1, w3 and w2 on the held rows, the
    cotangent NaN past them: nothing of the tail reaches a gradient."""
    operands = _operands(dtype)
    n = sum(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    held = (jnp.arange(operands[0].shape[0]) < n)[:, None]
    cot = jnp.asarray(np.random.RandomState(5).normal(
        size=operands[0].shape), dtype)

    def both(op, tail):
        ys, pull = jax.vjp(lambda *o: op(*o, sizes, act=act), *operands)
        return [ys] + list(pull(jnp.where(held, cot, tail).astype(dtype)))

    got, want = both(moe.moe_experts, jnp.nan), both(_plain_experts, 0.0)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    for name, g, w in zip(("ys", "rows", "w1", "w3", "w2"), got, want):
        assert g.dtype == w.dtype, name
        g, w = (np.asarray(x, np.float32) for x in (g, w))
        if name in ("ys", "rows"):
            g, w = g[:n], w[:n]
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max()
                                   if w.size else 0, err_msg=name)


def test_the_backward_holds_the_passes_and_no_add_over_the_buffer():
    """In the gradient's program the gate runs once forward, its transpose
    once and the row sum once, each a kernel that is given ``n``; no
    ``add_any`` is left for the two cotangents of ``rows``."""
    operands = _operands(jnp.float32)
    sizes = jnp.asarray((100, 0, 177), jnp.int32)
    text_ = str(jax.make_jaxpr(jax.grad(
        lambda *o: moe.moe_experts(*o, sizes).sum(), (0, 1, 2, 3)))(*operands))
    for kernel in ("moe_gate", "moe_gate_bwd", "moe_row_sum"):
        assert text_.count("name=%s\n" % kernel) + text_.count(
            "name=%s " % kernel) == 1, kernel
    assert not re.search(r"\[1280,\d+\] = add_any", text_)


# ---------------------------------------------------------------------------
# through the block
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _experts_op(fn):
    op = registry.OPS["_contrib_moe_experts"]
    was = op.fn
    op.fn = fn(was)
    try:
        yield
    finally:
        op.fn = was


def _block_and_inputs(act="silu"):
    """An expert layer that holds 2 of its 8 experts over 640 assignments: a
    quarter of the buffer is held, and its last block (rows 512-639) is past
    every pass's extent."""
    block = text.ExpertFFN(32, 8, 2, 24, experts_held=(2, 2), act=act,
                           shared=False, prefix="moe_")
    block.initialize(init=mx.init.Xavier())
    x = jnp.asarray(np.random.RandomState(6).normal(size=(1, 320, 32)),
                    jnp.float32)
    block(mx.nd.array(np.asarray(x)))
    params = [p for p in block.collect_params().values()
              if p.grad_req != "null"]
    return block, params, [p.data()._data for p in params], x


def _loss_and_grads(block, params, values, x):
    def loss(values, x):
        out, tc = pure_forward(block, params, values, x, training=True)
        return jnp.sum(jnp.sin(out)), (out, tc.aux_writes[id(block.counts)][1])

    (value, (out, load)), grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(values, x)
    return [out, value] + jax.tree.leaves(grads), load


@pytest.mark.parametrize("act", sorted(moe._GATES))
def test_a_block_whose_buffers_hold_nan_past_the_held_rows(act):
    """Under the interpreter what a pass leaves unwritten is NaN: the gate's
    result, both of its cotangents and the cotangent of the rows, from row
    512 on.  Output, loss and every gradient are finite and those of the
    block over the plain four-line op."""
    block, params, values, x = _block_and_inputs(act)
    got, load = _loss_and_grads(block, params, values, x)
    with _experts_op(lambda was: _plain_experts):
        want, _ = _loss_and_grads(block, params, values, x)
    assert len(got) == len(want) == 2 + len(params) + 1
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    assert 0 < np.asarray(load)[2:4].sum() < 512 - 128


@pytest.mark.parametrize("control,controls", [
    ("expert", glm47_flash.CONTROLS), ("float8", glm47_flash.CONTROLS),
    ("gate", smallthinker_21b.CONTROLS)])
def test_the_benchmarks_controls_of_the_op_still_change_the_result(
        control, controls):
    """``expert`` (one held expert's down projection zero), ``float8`` (the
    op's inputs rounded under a scale of the whole buffer: all of ``rows`` is
    written) and ``gate`` (SiLU where the model's gate is a ReLU) wrap the
    op's inputs and its ``act``: output and gradients stay finite and are
    another layer's."""
    target, replace = controls[control]
    assert target == "_contrib_moe_experts"
    block, params, values, x = _block_and_inputs("relu")
    sound, _ = _loss_and_grads(block, params, values, x)
    with _experts_op(replace):
        broken, _ = _loss_and_grads(block, params, values, x)
    for g in broken:
        assert np.isfinite(np.asarray(g)).all()
    out, was = np.asarray(broken[0]), np.asarray(sound[0])
    assert np.abs(out - was).max() > 1e-3 * np.abs(was).max()
