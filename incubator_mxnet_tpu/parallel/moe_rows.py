"""Pallas row movers of the no-drop expert layer (``parallel/moe.py``): the
work of each follows ``n``, the number of rows of the ``T * K`` buffer that
are in a group, which only the step itself knows.

A row that is scattered can be moved by one DMA only if it lies in a
leading, untiled dimension: Mosaic slices the tiled second-minor dimension
of a ``(rows, d)`` array in steps of 8.  So a row travels as a SLAB, ``sub``
x 128 words of 32 bits that hold its ``d`` values (bf16: value ``c`` in the
low half of word ``c`` and value ``c + sub * 128`` in the high half), a
whole number of (8, 128) tiles that lie together in HBM: 4 KiB for 2,048
bf16 values, 8 KiB for 2,560.  Only the slab is that wide.  The arrays of
rows and of tokens go into the kernels and come out of them at their own
width (up to whole tiles of 128 lanes, which every model's width is; the
tests' 16 is padded by the one ``_pad`` below), and the loops over a
slab's word-rows know from that static width which tiles a word-row holds
(``_each_word_row``): no pass over a whole buffer pads it to the slab's
width for a kernel or cuts the result back.

* :func:`to_slabs` turns the first ``n`` rows of a ``(rows, d)`` array into
  slabs; grid steps past ``n`` do nothing and write nothing back, and what
  a slab has past its row's width is zero bits or not written.  The two
  movers below make the slabs of their source themselves.
* :func:`rows_from_tokens` is ``out[r] = scale[r] * src[tok[r]]`` for ``r <
  n``, one DMA a row into a dense block, scaled in float32, and beside it
  ``sum(src[tok[r]] * ys[r])`` row by row.  Rows from ``n`` to the end of
  the block that holds ``n`` are zeros; the blocks after it are not
  written.
* :func:`tokens_from_rows` is ``out[t] = sum over the held assignments of t
  of w * src[row]``: the held assignments of a block of tokens (listed by
  one stable sort) gathered densely, and summed into their tokens by a
  product with a (token x row) matrix of the weights on the matrix unit,
  float32 throughout.
* :func:`on_held_rows` is an elementwise ``body`` over the rows below ``n``
  of one to three ``(rows, w)`` arrays, a dense block of rows a grid step,
  float32 inside and rounded once at the store (the expert op's gate, its
  backward, and the sum of its two row cotangents).  The block that holds
  ``n`` is worked whole; the blocks after it are not written.

On the CPU the kernels run through the Pallas interpreter, which fills what
a kernel leaves unwritten with NaN.  The package runs with x64 on: every
index in here is an explicit int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend

__all__ = ["to_slabs", "rows_from_tokens", "tokens_from_rows", "on_held_rows"]

_LANES = 128
_SUB = 8
#: rows of the buffer a grid step of the row kernels covers
_ROWS = 256
#: tokens a grid step of the token kernel covers, and the rows it gathers
#: at a time
_TOKENS = 128
_CHUNK = 128
#: an elementwise pass: the most rows a grid step covers (a step costs a
#: third of a microsecond whether it has rows or not), the rows its body is
#: given at a time, and the VMEM its blocks may take.  A kernel gets 16 MiB
#: unasked and every block stands there twice (the pipeline's two buffers);
#: what is left is the body's: the gate's backward keeps up to a dozen
#: float32 values an element alive, 12 x 32 x 2,560 x 4 B = 3.75 MiB at the
#: widest row a cell has, so 16 - 4 = 12.  (Blocks of 15 MiB, which 512
#: rows of five arrays of 1,536 or three of 2,560 bf16 values are, compile
#: for a described v5e too; 256 rows there cost 64 to 192 more grid steps
#: a pass, 0.07 ms, and leave the margin.)
_HELD_ROWS = 512
_HELD_FEW = 32
_HELD_VMEM = 12 * 2 ** 20
#: XLA tiles a one-dimensional int32 array by 1024: the indices reach a
#: kernel's scalar memory in blocks of a multiple of that
_INDICES = 1024

_I0 = np.int32(0)
_HIGH = np.uint32(0xFFFF0000)
_SIXTEEN = np.uint32(16)


def _up(a, b):
    return -(-a // b) * b


def _block(n, most):
    """Rows a grid step covers: ``most``, or all of a shorter array."""
    return most if n >= most else _up(n, _SUB)


def _carrier(dtype):
    """The dtype a row travels in: its own if that is bfloat16 or float32,
    else float32 (float16 fits; float64, which no TPU has, is rounded)."""
    return jnp.dtype(dtype if dtype == jnp.bfloat16 else jnp.float32)


def _geometry(d, dtype):
    """(values a word holds, the width ``w`` of the arrays the kernels take,
    word-rows of 128 lanes a row's slab has) for rows of ``d`` values that
    travel as ``dtype``.  ``w`` is ``d`` up to whole tiles of 128 lanes:
    every cell's own width, so no array is padded for a kernel.  Only the
    SLAB is wider: a whole number of (8, 128) tiles of words, so that a
    row's DMA is aligned, which is 1,024 words (2,048 two-byte values,
    1,024 float32) or a multiple.  A row of 2,048 bfloat16 values fills its
    slab; one of 1,536 lies in a slab of 2,048 and one of 2,560 in a slab
    of 4,096 (3,072 in float32), 60 % more bytes than the row where a DMA
    moves it and nowhere else: the words past ``w`` are never written and
    never read (``_each_word_row``)."""
    pack = 4 // _carrier(dtype).itemsize
    w = _up(d, _LANES)
    return pack, w, _up(w, _LANES * _SUB * pack) // pack // _LANES


def _bits(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def _f32(word):
    return jax.lax.bitcast_convert_type(word, jnp.float32)


def _lanes(tile):
    """The 128 columns of tile ``tile`` (an int32 that may be traced)."""
    return pl.ds(pl.multiple_of(tile * np.int32(_LANES), _LANES), _LANES)


def _word(x_ref, s, sub, pack, high):
    """Word-row ``s`` of every row's slab, from a (rows, w) block; without
    ``high`` a two-byte word's upper half is zero bits."""
    lo = _bits(x_ref[:, _lanes(s)])
    if pack == 1:
        return lo
    if not high:
        return lo >> _SIXTEEN
    hi = _bits(x_ref[:, _lanes(s + np.int32(sub))])
    return (lo >> _SIXTEEN) | (hi & _HIGH)


def _values(word, s, sub, pack, high):
    """((tile, its (rows, 128) float32 values), ...) of a slab's word-row."""
    if pack == 1:
        return ((s, _f32(word)),)
    low = (s, _f32(word << _SIXTEEN))
    return (low, (s + np.int32(sub), _f32(word & _HIGH))) if high else (low,)


def _each_word_row(sub, pack, w, body):
    """``body(s, high)`` for every word-row ``s`` of a slab that holds a tile
    of the ``w`` columns: tile ``s`` in the words' low halves (all of a
    float32 word) and, where ``high``, tile ``s + sub`` in the high halves of
    two-byte values.  Of 2,560 bfloat16 values (20 tiles, ``sub`` 16)
    word-rows 0-3 hold two tiles, 4-15 one; a slab that its row fills
    (``w`` = 2,048 bfloat16) is one range.  ONE traced loop body a range: a
    kernel unrolled over the word-rows takes eight times as long to trace
    and to lower, at every start of a process."""
    tiles = w // _LANES
    both = max(tiles - sub, 0) if pack == 2 else 0
    for first, end, high in ((0, both, True), (both, min(sub, tiles), False)):
        if first < end:
            _count(first, end, functools.partial(body, high=high))


def _count(first, end, body):
    """``body(s)`` for ``s`` in the static range [first, end)."""
    def step(s):
        body(s)
        return s + np.int32(1)

    # a while loop: under x64 a fori_loop with static bounds counts in int64
    jax.lax.while_loop(lambda s: s < np.int32(end), step, np.int32(first))


def _gather(idx_ref, first, count, slab_ref, buf, sem, sub):
    """Slabs ``idx_ref[first + j]`` into ``buf``'s slot ``j``, j < count:
    every copy started, then every copy awaited."""
    def copy(j):
        src = pl.multiple_of(idx_ref[first + j] * np.int32(sub), sub)
        dst = pl.multiple_of(j * np.int32(sub), sub)
        return pltpu.make_async_copy(slab_ref.at[pl.ds(src, sub)],
                                     buf.at[pl.ds(dst, sub)], sem)

    def start(j, c):
        copy(j).start()
        return c

    def wait(j, c):
        copy(j).wait()
        return c

    jax.lax.fori_loop(_I0, count, start, _I0)
    jax.lax.fori_loop(_I0, count, wait, _I0)


def _last_live(n_ref, rows):
    """Index-map form of "the block that holds row n - 1": a step past it
    names it again, so nothing is fetched for it and nothing written."""
    return jnp.maximum(jax.lax.div(n_ref[0] - np.int32(1), np.int32(rows)),
                       _I0)


def _live_block(rows):
    """The index map of a (rows, w) block of a grid over the buffer whose
    steps past ``n`` name the last live block again."""
    def block(i, n_ref):
        return jnp.minimum(i, _last_live(n_ref, rows)), _I0

    return block


def _pad(x, rows, cols):
    extra = ((0, rows - x.shape[0]), (0, cols - x.shape[1]))
    return jnp.pad(x, extra) if any(e for _, e in extra) else x


def _n(n):
    return jnp.asarray(n, jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# rows -> slabs
# ---------------------------------------------------------------------------

def _slab_kernel(n_ref, x_ref, o_ref, *, rows, sub, pack):
    @pl.when(pl.program_id(0) * np.int32(rows) < n_ref[0])
    def _():
        def one(s, high):
            o_ref[pl.ds(s, rows, stride=sub), :] = _word(x_ref, s, sub, pack,
                                                         high)

        _each_word_row(sub, pack, x_ref.shape[1], one)


def to_slabs(x, n):
    """The first ``n`` rows of ``x`` (rows, d) as slabs: (rows' * sub, 128)
    uint32, row ``r``'s slab at word-rows ``[r * sub, (r + 1) * sub)``.  The
    slabs of rows from the end of ``n``'s block on are not written, nor a
    slab's words past the row's own width."""
    pack, w, sub = _geometry(x.shape[1], x.dtype)
    rows = _block(x.shape[0], _ROWS)
    rp = _up(x.shape[0], rows)
    block = _live_block(rows)
    return pl.pallas_call(
        functools.partial(_slab_kernel, rows=rows, sub=sub, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rp // rows,),
            in_specs=[pl.BlockSpec((rows, w), block)],
            out_specs=pl.BlockSpec((rows * sub, _LANES), block)),
        out_shape=jax.ShapeDtypeStruct((rp * sub, _LANES), jnp.uint32),
        interpret=_backend.pallas_interpret(),
        name="moe_slabs",
    )(_n(n), _pad(x.astype(_carrier(x.dtype)), rp, w))


# ---------------------------------------------------------------------------
# slabs -> rows in expert order
# ---------------------------------------------------------------------------

def _rows_kernel(n_ref, tok_ref, slab_ref, scale_ref, ys_ref, o_ref, dot_ref,
                 buf, dots, sem, *, rows, per, sub, pack):
    step = pl.program_id(0)
    first = step * np.int32(rows)

    @pl.when(first < n_ref[0])
    def _():
        count = jnp.minimum(n_ref[0] - first, np.int32(rows))
        _gather(tok_ref, jax.lax.rem(step, np.int32(per)) * np.int32(rows),
                count, slab_ref, buf, sem, sub)
        # the slots past the last row hold what the scratch held before
        live = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < count
        dots[...] = jnp.zeros(dots.shape, jnp.float32)

        def one(s, high):
            word = buf[pl.ds(s, rows, stride=sub), :]
            for tile, v in _values(word, s, sub, pack, high):
                v = jnp.where(live, v, jnp.float32(0))
                dots[...] += v * ys_ref[:, _lanes(tile)].astype(jnp.float32)
                o_ref[:, _lanes(tile)] = (v * scale_ref[...]).astype(
                    o_ref.dtype)

        _each_word_row(sub, pack, o_ref.shape[1], one)
        dot_ref[...] = jnp.sum(dots[...], 1, keepdims=True)


def rows_from_tokens(src, tok, n, scale, ys):
    """``(scale[r] * src[tok[r]], sum(src[tok[r]] * ys[r]))`` for ``r < n``:
    ``src`` (T, width), ``scale`` (R,) float32, ``ys`` (R, width); the rows
    in ``ys``'s dtype, scaled in float32, and the sums (R,) float32.  Zeros
    from row ``n`` to the end of its block; later blocks are not written."""
    r, width = ys.shape
    pack, w, sub = _geometry(width, ys.dtype)
    rows = _block(r, _ROWS)
    rp = _up(r, _INDICES if rows == _ROWS else rows)
    per = min(rp, _INDICES) // rows
    block = _live_block(rows)

    def line(i, n_ref):
        return (jax.lax.div(block(i, n_ref)[0], np.int32(per)),)

    wide = pl.BlockSpec((rows, w), block)
    thin = pl.BlockSpec((rows, 1), block)
    carrier = _carrier(ys.dtype)
    out, dots = pl.pallas_call(
        functools.partial(_rows_kernel, rows=rows, per=per, sub=sub,
                          pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rp // rows,),
            in_specs=[pl.BlockSpec((rows * per,), line,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY), thin, wide],
            out_specs=[wide, thin],
            scratch_shapes=[pltpu.VMEM((rows * sub, _LANES), jnp.uint32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((rp, w), carrier),
                   jax.ShapeDtypeStruct((rp, 1), jnp.float32)],
        interpret=_backend.pallas_interpret(),
        name="moe_rows",
    )(_n(n), jnp.pad(tok.astype(jnp.int32), (0, rp - r)),
      to_slabs(src.astype(ys.dtype), src.shape[0]),
      _pad(scale.astype(jnp.float32).reshape(r, 1), rp, 1),
      _pad(ys.astype(carrier), rp, w))
    return out[:r, :width].astype(ys.dtype), dots[:r, 0]


# ---------------------------------------------------------------------------
# slabs -> tokens
# ---------------------------------------------------------------------------

def _token_lists(row, n, weights):
    """The held assignments (``row < n``) a block of tokens at a time, held
    before not held: ``(rows, token within the block, weights or None)``,
    each (blocks, L), of which the first ``counts`` (blocks,) of a block
    mean something.  ONE stable sort of int32 keys that carries the three as
    it goes: a gather of T * K scalars by a sorted index costs XLA ten
    sorts."""
    t, k = row.shape
    tokens = _block(t, _TOKENS)
    tp = _up(t, tokens)
    blocks = tp // tokens
    extra = ((0, tp - t), (0, 0))
    held = jnp.pad(row < n, extra)
    token = jnp.arange(tp, dtype=jnp.int32)
    key = (2 * (token // np.int32(tokens)))[:, None] + (~held).astype(
        jnp.int32)
    carried = [jnp.broadcast_to((token % np.int32(tokens))[:, None], (tp, k)),
               jnp.pad(row, extra)]
    if weights is not None:
        carried.append(jnp.pad(weights.astype(jnp.float32), extra))
    _, *carried = jax.lax.sort(
        [a.reshape(-1) for a in [key] + carried], num_keys=1, is_stable=True)
    counts = jnp.sum(held.reshape(blocks, tokens * k), 1, dtype=jnp.int32)
    return [a.reshape(blocks, tokens * k) for a in carried], counts


def _parts(p, pack, weighted):
    """The (token x slot) matrix as the matrix unit takes it.  Rows that
    travel as bfloat16 are exact there, so a float32 product with them is
    three bfloat16 products, of the matrix's leading, middle and last eight
    bits (one where the matrix holds only 0 and 1); float32 rows take the
    unit's own float32 product."""
    if pack == 1:
        return (p,)
    parts = [p.astype(jnp.bfloat16)]
    for _ in range(2 if weighted else 0):
        p = p - parts[-1].astype(jnp.float32)
        parts.append(p.astype(jnp.bfloat16))
    return tuple(parts)


def _product(parts, v, pack):
    kind = dict(precision=jax.lax.Precision.HIGHEST) if pack == 1 else dict(
        precision=jax.lax.Precision.DEFAULT)
    v = v.astype(parts[0].dtype)
    return sum(jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        **kind) for p in parts)


def _tokens_kernel(cnt_ref, row_ref, tokl_ref, *refs, tokens, sub, pack,
                   weighted):
    if weighted:
        w_ref, slab_ref, o_ref, buf, acc, sem = refs
    else:
        slab_ref, o_ref, buf, acc, sem = refs
    count = cnt_ref[pl.program_id(0)]
    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    token = jax.lax.broadcasted_iota(jnp.int32, (tokens, _CHUNK), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _CHUNK), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, 1), 0)

    def chunk(c, carry):
        first = c * np.int32(_CHUNK)
        m = jnp.minimum(count - first, np.int32(_CHUNK))
        _gather(row_ref, first, m, slab_ref, buf, sem, sub)
        # (token, slot): the slot's weight where the slot is that token's
        hit = (token == tokl_ref[0, pl.ds(c, 1), :]) & (lane < m)
        w = w_ref[0, pl.ds(c, 1), :] if weighted else jnp.float32(1)
        parts = _parts(jnp.where(hit, w, jnp.float32(0)), pack, weighted)
        def one(s, high):
            word = buf[pl.ds(s, _CHUNK, stride=sub), :]
            for tile, v in _values(word, s, sub, pack, high):
                # a stale slot may hold NaN, and 0 * NaN is NaN
                v = jnp.where(slot < m, v, jnp.float32(0))
                acc[:, _lanes(tile)] += _product(parts, v, pack)

        _each_word_row(sub, pack, acc.shape[1], one)
        return carry

    chunks = jax.lax.div(count + np.int32(_CHUNK - 1), np.int32(_CHUNK))
    jax.lax.fori_loop(_I0, chunks, chunk, _I0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def tokens_from_rows(src, row, n, weights=None):
    """``out[t] = sum over the k with row[t, k] < n of weights[t, k] *
    src[row[t, k]]`` (weight 1 without ``weights``); (T, width) of ``src``'s
    dtype, zero where a token holds none.  Of ``src`` (R, width) only the
    rows below ``n`` are read.  Weighted and summed in float32, rounded
    once."""
    width, dtype = src.shape[1], src.dtype
    pack, w, sub = _geometry(width, dtype)
    t, k = row.shape
    (tokl, rows, *ws), counts = _token_lists(row, n, weights)
    blocks, length = rows.shape
    tokens = length // k
    lp = _up(length, max(_CHUNK, _INDICES))
    shape = (blocks, lp // _CHUNK, _CHUNK)

    def listed(a):
        return jnp.pad(a, ((0, 0), (0, lp - length)))

    meta = pl.BlockSpec((1,) + shape[1:], lambda i, c: (i, _I0, _I0))
    operands = [counts, listed(rows).reshape(-1)]
    operands += [listed(a).reshape(shape) for a in [tokl] + ws]
    in_specs = [pl.BlockSpec((lp,), lambda i, c: (i,),
                             memory_space=pltpu.SMEM)] + [meta] * (1 + len(ws))
    out = pl.pallas_call(
        functools.partial(_tokens_kernel, tokens=tokens, sub=sub, pack=pack,
                          weighted=bool(ws)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, w), lambda i, c: (i, _I0)),
            scratch_shapes=[pltpu.VMEM((_CHUNK * sub, _LANES), jnp.uint32),
                            pltpu.VMEM((tokens, w), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((blocks * tokens, w),
                                       _carrier(dtype)),
        interpret=_backend.pallas_interpret(),
        name="moe_tokens",
    )(*operands, to_slabs(src, n))
    return out[:t, :width].astype(dtype)


# ---------------------------------------------------------------------------
# an elementwise pass over the rows below n
# ---------------------------------------------------------------------------

def _held_kernel(n_ref, *refs, rows, ins, body):
    @pl.when(pl.program_id(0) * np.int32(rows) < n_ref[0])
    def _():
        # a few rows at a time: what ``body`` makes on its way is then a few
        # registers' worth and not a second block in VMEM
        few = math.gcd(rows, _HELD_FEW)

        def some(i):
            at = pl.ds(pl.multiple_of(i * np.int32(few), few), few)
            results = jax.tree.leaves(body(*(
                x_ref[at, :].astype(jnp.float32) for x_ref in refs[:ins])))
            for o_ref, v in zip(refs[ins:], results):
                o_ref[at, :] = v.astype(o_ref.dtype)

        _count(0, rows // few, some)


def _held_block(r, w, arrays, itemsize):
    """Rows a grid step covers of ``arrays`` operands and results of ``r``
    rows of ``w`` values: a power of two, so that it divides a buffer, and
    no fewer than a tile of the narrowest dtype holds."""
    fit = _HELD_VMEM // (2 * arrays * w * itemsize)
    if fit < _HELD_FEW:
        raise ValueError(
            "on_held_rows: %d arrays of width %d leave a block %d rows of "
            "%d MiB, and the pass works %d at a time"
            % (arrays, w, fit, _HELD_VMEM >> 20, _HELD_FEW))
    return _block(r, min(_HELD_ROWS, 1 << fit.bit_length() - 1))


def on_held_rows(body, outs, n, *arrays, name):
    """``body(*arrays)``, elementwise, for the rows below ``n``: ``(rows,
    d)`` arrays of one shape and dtype in, a tuple of ``outs`` ``(rows, d)``
    arrays of that dtype out (``body`` returns one array or a tuple).
    ``body``
    sees float32 rows and its results are rounded once, at the store.  The
    rows from ``n`` to the end of ``n``'s block get what ``body`` makes of
    what the arrays hold there; the blocks after it are not written.  A
    grid step covers as many rows as keep a block of every operand and
    result, twice over, inside ``_HELD_VMEM``, ``_HELD_ROWS`` at most."""
    r, d = arrays[0].shape
    dtype = arrays[0].dtype
    carrier = _carrier(dtype)
    w = _up(d, _LANES)
    rows = _held_block(r, w, len(arrays) + outs, carrier.itemsize)
    rp = _up(r, rows)
    spec = pl.BlockSpec((rows, w), _live_block(rows))
    results = pl.pallas_call(
        functools.partial(_held_kernel, rows=rows, ins=len(arrays),
                          body=body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rp // rows,),
            in_specs=[spec] * len(arrays), out_specs=[spec] * outs),
        out_shape=[jax.ShapeDtypeStruct((rp, w), carrier)] * outs,
        interpret=_backend.pallas_interpret(),
        name=name,
    )(_n(n), *(_pad(x.astype(carrier), rp, w) for x in arrays))
    return tuple(o[:r, :d].astype(dtype) for o in results)
