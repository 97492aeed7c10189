"""The Mamba-2 mixer's causal depthwise convolution with its SiLU,
``silu(causal_conv1d(x, w, b)).astype(dtype)``, as two Pallas kernels behind a
``jax.custom_vjp`` (``conv_fwd``, ``conv_bwd``; one jitted wrapper each, so a
program lowers each body once however many layers call it).
``ops/ssd.py:causal_conv1d`` is the ``jax.numpy`` form: what the CPU runs, what
a shape the kernels do not take falls to, and the kernels' parity reference.

Why kernels. The ``jax.numpy`` form pads a float32 copy of ``x`` and takes
``K`` shifted slices of it; on the chip autodiff's transpose of the pad and the
slices writes float32 ``[B, T, C]`` arrays, and each of the ``K`` tap
gradients reads ``x`` and ``dy`` again (PERF.md §5: 0.14 ns an element a layer,
some eight times one pass over the operands). The kernels also read ``xBC``
straight out of the mixer's input projection, where the ``jax.numpy`` form's
slice of it is fused and a Mosaic call's would be a copy.

The kernels take and give their operands with time as the last axis
(``[B, C, T]``), which is how the compiler holds the mixer's activations on the
TPU (``ops/ssd.py``'s kernels take them so too), so the wrappers' transposes
are bitcasts there. A grid step holds one block of channels' whole sequences
in VMEM, so each call reads its operands from HBM once and writes its result
once, and the ``K − 1`` steps before a block's first are never fetched twice.
Inside, it walks a row group of 16 channels a lane chunk of up to 1,024 steps
at a time: a tap is the chunk turned along the lanes (``pltpu.roll``) with the
last lane tile of the chunk before in front of it (zeros before the sequence).
The turns are the kernels' costly part (a cross-lane operation each), so ``x``
is turned as 32-bit words, a bfloat16 channel pair to a word, and widened
after; the taps' and bias's lane broadcasts are made once a row group. The
chunk loop is unrolled whole (Mosaic unrolls one trip or all): a trip of one
chunk is a chain of dependent operations that the bundle scheduler cannot
overlap with the next, and unrolled it interleaves them (on the chip, PR 39:
forward 2.4 and backward 2.1 times as fast as one 512-step chunk a trip).

Same mathematics, rounded at the same places: float32 inside; the taps summed
``j = 0 … K−1`` in turn as the ``jax.numpy`` form sums them, then the bias,
then the SiLU; ``y`` leaves in ``dtype``. The backward recomputes the
pre-activation from ``x``, takes ``dpre = dy · silu′(pre)`` in float32 and
returns ``dx = Σ_j w_j · dpre_{t+K−1−j}`` in ``x``'s dtype, ``dw_j = Σ x_{t−K+1+j}
· dpre_t`` and ``db = Σ dpre`` in float32: partial sums a batch row and
channel, which ``jax.numpy`` sums over the rows (``[B, C, K]``, kilobytes).
:func:`conv_tiles` says whether a shape is taken; ``ops/dispatch.py:conv_fn``
picks the path and records it. Under a multi-device mesh the kernels run
inside ``jax.shard_map``, a chip's rows each.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_training_pytorch_tpu.ops.pallas import _ambient_shard_spec, resolve_interpret

__all__ = ["causal_conv_silu", "conv_tiles", "rows_unsplit"]

_F32 = jnp.float32
_LANE = 128
_ROWS = 16  # channels a row group: one bfloat16 sublane tile
_BLOCK_ELEMENTS = 2**18  # channels x steps a grid step holds, about (512 KiB of a bfloat16 operand)
_VMEM_CEILING = 64 * 2**20  # the most a kernel is compiled under
_LANE_CHUNKS = (1024, 512, 256, _LANE)


def _lanes(t: int) -> int:
    """Steps a lane chunk: the widest of ``_LANE_CHUNKS`` that divides ``T``."""
    return next(n for n in _LANE_CHUNKS if t % n == 0)


def _channel_block(c: int, t: int, start: int = 0) -> int:
    """Channels a grid step: the most, in whole row groups dividing ``C`` and
    the offset ``start`` of the convolution's channels in the operand, whose
    whole sequences are about ``_BLOCK_ELEMENTS`` (64 at granite-4.0-h's 4,352
    channels and T=4096, 32 at nemotron-3-nano's 6,144 and T=8192)."""
    want = max(_ROWS, _BLOCK_ELEMENTS // t)
    return max(cb for cb in range(_ROWS, min(c, want) + 1, _ROWS) if c % cb == 0 and start % cb == 0)


def _vmem_bytes(cb: int, t: int, itemsize: int, backward: bool) -> int:
    """The scoped-VMEM limit a kernel is compiled under, reckoned from its
    blocks: the sequences (``x`` and ``y``; ``x``, ``dy`` and ``dx``) and the
    per-channel columns (weights, bias; the partial sums), lanes padded to
    128, all double-buffered, and a row group's float32 temporaries. Never
    under the compiler's 16 MiB default: a ceiling, not a reservation."""
    sequences = cb * t * itemsize * (3 if backward else 2)
    columns = cb * _LANE * 4 * (4 if backward else 2)
    temporaries = 32 * _ROWS * (_LANE_CHUNKS[0] + _LANE) * 4
    return max(16 * 2**20, 2 * (sequences + columns) + temporaries)


def conv_tiles(c: int, t: int, k: int, start: int = 0) -> Optional[str]:
    """None where :func:`causal_conv_silu` takes the shape, else why it does
    not: ``T`` is whole lane tiles, the channels (and their offset ``start`` in
    the operand) whole row groups, the ``K − 1`` steps a tap reaches back lie in
    the one lane tile carried from the chunk before, and a row group's whole
    sequences fit the kernels' VMEM."""
    if not 1 <= k <= _LANE + 1:
        return f"{k} taps: a tap reaches back at most one lane tile ({_LANE} steps)"
    if t % _LANE:
        return f"T {t} is no multiple of {_LANE}"
    if c % _ROWS or start % _ROWS:
        return f"{c} channels from channel {start} are no whole groups of {_ROWS}"
    if _vmem_bytes(_ROWS, t, 4, backward=True) > _VMEM_CEILING:
        return f"T {t}: {_ROWS} channels' whole sequences do not fit the backward's {_VMEM_CEILING >> 20} MiB of VMEM"
    return None


def rows_unsplit(rows: int) -> Optional[str]:
    """Why the kernels cannot run under the ambient mesh, or None: a Mosaic
    call has no partitioning rule, so over more than one device the rows have
    to split over the batch axes (``shard_map``), and these do not."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.manual_axes or mesh.size == 1:
        return None
    if _ambient_shard_spec((rows, 1, 1, 1)) is None:
        return f"{rows} rows do not split over the batch axes of a mesh of {mesh.size} devices"
    return None


def _turned(v, s):
    """``v`` turned ``s`` lanes towards the end (``s`` may be negative)."""
    s %= v.shape[1]
    return v if s == 0 else pltpu.roll(v, s, 1)


def _tiles_sum(v):
    """``[rows, n·128] -> [rows, 128]``: the lane tiles added, a partial sum kept in registers."""
    return sum(v[:, i * _LANE:(i + 1) * _LANE] for i in range(v.shape[1] // _LANE))


def _taps(x, tail, k, dtype):
    """The ``K`` taps of one chunk, float32 ``[rows, L]`` each: tap ``j`` is
    ``x_{t−K+1+j}``, the chunk turned ``K−1−j`` lanes with ``tail`` (the last
    lane tile before it) in front, so its first lanes come from there.
    ``x`` and ``tail`` come as 32-bit words (:func:`_words`), so a rotation
    moves two bfloat16 channels at once, and are widened after: no bit moves."""
    ext = jnp.concatenate([tail, x], axis=1)  # [rows, 128 + L]
    return [_widened(_turned(ext, k - 1 - j), dtype)[:, _LANE:] for j in range(k - 1)] + [_widened(x, dtype)]


def _words(ref):
    """A sequence block's view as 32-bit words: a bfloat16 ``[.., 2n, T]``
    as uint32 ``[.., n, T]`` (a word holds a channel pair, as the packed
    register does)."""
    return ref if ref.dtype.itemsize == 4 else ref.bitcast(jnp.uint32)


def _widened(v, dtype):
    """32-bit words back to their ``dtype`` channels, float32."""
    if v.dtype != dtype:
        v = pltpu.bitcast(v, dtype)
    return v.astype(_F32)


def _last_tile(xw, words, i, lanes):
    """Chunk ``i``'s last lane tile, the ``tail`` of chunk ``i + 1`` (read again
    from VMEM: Mosaic cuts no lane tile out of a loaded word view)."""
    return xw[0, words, pl.ds(pl.multiple_of(i * lanes + lanes - _LANE, _LANE), _LANE)]


def _columns(w_ref, b_ref, rows, lanes):
    """A row group's taps and bias spread along ``L`` lanes once, outside the
    chunk loop (a lane broadcast is a cross-lane operation; in the loop it
    would be made again every chunk)."""
    w = w_ref[rows, :].astype(_F32)
    return ([jnp.broadcast_to(w[:, j:j + 1], (_ROWS, lanes)) for j in range(w.shape[1])],
            jnp.broadcast_to(b_ref[rows, :].astype(_F32), (_ROWS, lanes)))


def _pre(taps, w, b):
    """``b + Σ_j w_j ⊙ tap_j``, the taps summed in turn from ``j = 0`` as ``causal_conv1d`` sums them."""
    acc = w[0] * taps[0]
    for j in range(1, len(taps)):
        acc = acc + w[j] * taps[j]
    return acc + b


def _conv_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, lanes):
    """One block of channels: ``y = silu(b + Σ_j w_j ⊙ x_{t−K+1+j})`` a row
    group and a lane chunk at a time, the chunk's last lane tile carried."""
    chunks = x_ref.shape[2] // lanes
    xw, pack = _words(x_ref), 4 // x_ref.dtype.itemsize
    for r in range(0, x_ref.shape[1], _ROWS):
        rows, words = pl.ds(r, _ROWS), pl.ds(r // pack, _ROWS // pack)
        w, b = _columns(w_ref, b_ref, rows, lanes)

        def chunk(i, tail, rows=rows, words=words, w=w, b=b):
            at = pl.ds(pl.multiple_of(i * lanes, lanes), lanes)
            pre = _pre(_taps(xw[0, words, at], tail, len(w), x_ref.dtype), w, b)
            y_ref[0, rows, at] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)  # silu, as jax.nn.silu
            return _last_tile(xw, words, i, lanes)

        jax.lax.fori_loop(0, chunks, chunk, jnp.zeros((_ROWS // pack, _LANE), xw.dtype), unroll=True)


def _conv_bwd_kernel(x_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref, db_ref, *, lanes):
    """One block of channels, the chunks in the forward's order: chunk ``i``'s
    ``dpre`` from its recomputed pre-activation, and ``dx`` of chunk ``i − 1``,
    which reads ``dpre`` up to ``K − 1`` steps into chunk ``i`` (carried: the
    ``dpre`` of the chunk before and the ``x`` tile before the chunk). ``dw``
    and ``db`` are lane-tile partial sums in registers, summed over the lanes
    once a row group."""
    chunks = x_ref.shape[2] // lanes
    k = w_ref.shape[1]

    def dx_of(d, d_next, w):
        """``Σ_j w_j ⊙ dpre_{t+K−1−j}`` over ``d``'s chunk, ``d_next``'s first lane tile after it."""
        ext = jnp.concatenate([d, d_next[:, :_LANE]], axis=1)  # [rows, L + 128]
        acc = w[0] * _turned(ext, -(k - 1))[:, :lanes]
        for j in range(1, k):
            acc = acc + w[j] * _turned(ext, -(k - 1 - j))[:, :lanes]
        return acc

    xw, pack = _words(x_ref), 4 // x_ref.dtype.itemsize
    for r in range(0, x_ref.shape[1], _ROWS):
        rows, words = pl.ds(r, _ROWS), pl.ds(r // pack, _ROWS // pack)
        w, b = _columns(w_ref, b_ref, rows, lanes)

        def chunk(i, carry, rows=rows, words=words, w=w, b=b):
            tail, d_before, dw, db = carry
            at = pl.ds(pl.multiple_of(i * lanes, lanes), lanes)
            taps = _taps(xw[0, words, at], tail, k, x_ref.dtype)
            tail = _last_tile(xw, words, i, lanes)
            pre = _pre(taps, w, b)
            sig = jax.nn.sigmoid(pre)
            d = dy_ref[0, rows, at].astype(_F32) * (sig * (1.0 + pre * (1.0 - sig)))  # dpre = dy · silu′(pre)

            @pl.when(i > 0)
            def _():
                before = pl.ds(pl.multiple_of((i - 1) * lanes, lanes), lanes)
                dx_ref[0, rows, before] = dx_of(d_before, d, w).astype(dx_ref.dtype)

            return tail, d, [a + _tiles_sum(tap * d) for a, tap in zip(dw, taps)], db + _tiles_sum(d)

        zeros = jnp.zeros((_ROWS, _LANE), _F32)
        _, d_last, dw, db = jax.lax.fori_loop(
            0, chunks, chunk, (jnp.zeros((_ROWS // pack, _LANE), xw.dtype), jnp.zeros((_ROWS, lanes), _F32), [zeros] * k, zeros),
            unroll=True)
        dx_ref[0, rows, pl.ds((chunks - 1) * lanes, lanes)] = dx_of(d_last, zeros, w).astype(dx_ref.dtype)  # nothing after T
        for j in range(k):
            dw_ref[0, rows, j:j + 1] = jnp.sum(dw[j], axis=1, keepdims=True)
        db_ref[0, rows, :] = jnp.sum(db, axis=1, keepdims=True)


def _time_last(v):
    """``[B, T, C]`` <-> ``[B, C, T]``."""
    return v.transpose(0, 2, 1)


def _specs(cb, t, k, first):
    """The block specs both kernels share; ``first`` is the block of the
    operand's channels where the convolution's begin (``x`` is read straight
    out of the projection that holds it, never copied out)."""
    return {
        "x": pl.BlockSpec((1, cb, t), lambda bi, ci: (bi, first + ci, 0)),
        "seq": pl.BlockSpec((1, cb, t), lambda bi, ci: (bi, ci, 0)),
        "w": pl.BlockSpec((cb, k), lambda bi, ci: (ci, 0)),
        "col": pl.BlockSpec((cb, 1), lambda bi, ci: (ci, 0)),
        "dw": pl.BlockSpec((1, cb, k), lambda bi, ci: (bi, ci, 0)),
        "db": pl.BlockSpec((1, cb, 1), lambda bi, ci: (bi, ci, 0)),
    }


def _call(kernel, name, x, w, b, start, out_shape, in_specs, out_specs, extra, backward, interpret):
    """One of the two ``pallas_call``s over ``(rows, channel blocks)``, both
    axes independent."""
    rows, t, _ = x.shape
    k, c = w.shape
    cb = _channel_block(c, t, start)
    spec = _specs(cb, t, k, start // cb)
    itemsize = max(v.dtype.itemsize for v in (x,) + extra + tuple(out_shape))
    return pl.pallas_call(
        functools.partial(kernel, lanes=_lanes(t)),
        grid=(rows, c // cb),
        in_specs=[spec[s] for s in in_specs],
        out_specs=[spec[s] for s in out_specs],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=_vmem_bytes(cb, t, itemsize, backward)),
        interpret=interpret,
        name=name,
    )(_time_last(x), *(_time_last(v) for v in extra), w.astype(_F32).T, b.astype(_F32)[:, None])


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _fwd_call(x, w, b, start, dtype, interpret):
    """``y`` ``[B, T, C]`` in ``dtype`` from channels ``start … start+C`` of
    ``x``. Jitted, as :func:`_bwd_call` is, so a program traces and lowers the
    body once however many layers call it; every operation sits in a scope
    ``mamba_conv`` of its own, which is what reaches the backward half a
    ``custom_vjp`` does not give its call site's scopes
    (``mamba_conv_time_share`` reads the scope)."""
    rows, t, _ = x.shape
    c = w.shape[1]
    with jax.named_scope("mamba_conv"):
        (y,) = _call(_conv_fwd_kernel, "conv_fwd", x, w, b, start, [jax.ShapeDtypeStruct((rows, c, t), dtype)],
                     ["x", "w", "col"], ["seq"], (), False, interpret)
        return _time_last(y)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bwd_call(x, w, b, dy, start, interpret):
    """``(dx, dw, db)`` from ``dy`` and the forward's inputs; ``dx`` has
    ``x``'s shape, zeros outside the convolution's channels (a pad that the
    compiler folds into the sum of ``x``'s cotangents)."""
    rows, t, f = x.shape
    k, c = w.shape
    with jax.named_scope("mamba_conv"):
        dx, dw, db = _call(_conv_bwd_kernel, "conv_bwd", x, w, b, start,
                           [jax.ShapeDtypeStruct((rows, c, t), x.dtype), jax.ShapeDtypeStruct((rows, c, k), _F32),
                            jax.ShapeDtypeStruct((rows, c, 1), _F32)],
                           ["x", "seq", "w", "col"], ["seq", "dw", "db"], (dy,), True, interpret)
        dx = jnp.pad(_time_last(dx), ((0, 0), (0, 0), (start, f - start - c)))
        return dx, jnp.sum(dw, axis=0).T.astype(w.dtype), jnp.sum(db, axis=(0, 2)).astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, w, b, start, dtype, interpret):
    return _fwd_call(x, w, b, start, dtype, interpret)


def _conv_fwd(x, w, b, start, dtype, interpret):
    return _fwd_call(x, w, b, start, dtype, interpret), (x, w, b)


def _conv_bwd(start, dtype, interpret, inputs, dy):
    return _bwd_call(*inputs, dy, start, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, w, b, *, start: int = 0, dtype=None, interpret: Optional[bool] = None) -> jax.Array:
    """``silu(causal_conv1d(x[..., start:start+C], w, b)).astype(dtype)`` from
    the Pallas kernels, with their written backward. ``x``: ``[B, T, F]``, the
    convolution's ``C`` channels from ``start`` on (the Mamba-2 mixer hands its
    whole input projection, so nothing is copied out of it); ``w``: ``[K, C]``
    (tap ``K−1`` meets the current step); ``b``: ``[C]``; ``dtype``: ``y``'s
    (``x``'s if None). The shape must tile (:func:`conv_tiles`);
    ``interpret=None`` auto-selects (``ops.pallas.resolve_interpret``). Under
    an ambient multi-device mesh the kernels run inside ``jax.shard_map`` with
    the rows split over the batch axes (:func:`rows_unsplit` says where they
    cannot) and ``w``, ``b`` whole; ``shard_map``'s transpose sums ``dw``,
    ``db`` over the rows' chips."""
    rows, t, _ = x.shape
    refused = conv_tiles(w.shape[1], t, w.shape[0], start) or rows_unsplit(rows)
    if refused:
        raise ValueError(f"causal_conv_silu: {refused}")
    dtype, interpret = jnp.dtype(dtype or x.dtype), resolve_interpret(interpret)

    def kernels(x, w, b):
        return _conv(x, w, b, start, dtype, interpret)

    spec = _ambient_shard_spec((rows, t, 1, 1))
    if spec is not None:
        kernels = jax.shard_map(kernels, in_specs=(P(spec[0]), P(), P()), out_specs=P(spec[0]), check_vma=False)
    return kernels(x, w, b)
