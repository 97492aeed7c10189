"""The routed expert layer's row movements as two Pallas kernels whose work
follows the live pairs, not the pairs' buffer.

``parallel/moe.py:HeldExpertsMlp`` sorts the (token, choice) pairs of the
experts a chip holds to the front of a static buffer of ``N·k`` rows, which has
to hold every pair because nothing is dropped, and fills a few per cent of it.
Four movements surround the grouped products: the tokens' rows into the buffer
and back (dispatch, combine), and the transposes of the two. Their
``jax.numpy`` forms (``moe.py:_rows_in`` … ``_rows_out_bwd``, which stay the
definition these kernels are held to) gather, mask and sum over all ``N·k``
pairs. The two kernels here do one row's work a live pair:

:func:`rows_from_table`, the buffer's side: ``out[r] = w[src[r]] · table[src[r] // k]``
for ``r < n_live``. A grid step is a tile of the buffer's rows; a tile that
starts past ``n_live`` does nothing, and its output block is clamped to the last
live tile's, so it is neither fetched nor flushed: **rows of tiles past the last
live one are never written** and hold whatever the allocation held (``fill``
gives them a value: the tests' NaN). The last live tile is zeros past ``n_live``.
With ``dot_with`` the same visit also takes ``⟨dot_with[r], table[src[r] // k]⟩`` a
row (the combine's transpose needs it for the weights' gradient, and has both
operands in VMEM here) and puts it at the row's pair among the tokens' ``[N, k]``.

:func:`tokens_from_rows`, the tokens' side: ``out[t] = Σ_j live[t, j] · w[t, j] ·
rows[dest[t, j]]``, accumulated in float32 in the order ``j = 0 … k−1``. A grid
step is a tile of tokens whose sums start as zeros in VMEM; the live pairs are
walked as one token-major list (:func:`live_pairs`: a sort of the pairs'
numbers), a tile's stretch of it given by a table of starts.

Both fetch a row from HBM by an asynchronous copy, ``_IN_FLIGHT`` of them in
flight, and both work a row at a time on the scalar core's word. A copy
moves the row's **group of eight**: Mosaic takes a slice of a table in HBM only
where it starts and ends at the ``(8, 128)`` tile, so a row costs eight rows'
bytes, which at a few per cent of live rows is still a few per cent of the
buffer's. A row of a bfloat16 table is the low or the high half of the 32-bit
words of a packed pair of rows: it is read through the 32-bit view of the
copied group and widened by a shift.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_pytorch_tpu.ops.pallas import resolve_interpret

__all__ = ["live_pairs", "rows_from_table", "rows_refused", "rows_tile", "tokens_from_rows"]

_F32 = jnp.float32
_U32 = jnp.uint32
_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))  # what a table's rows may be
_TILE = 256  # rows of the buffer, or tokens, a grid step
_GROUP = 8  # rows a copy: a slice of a table in HBM starts and ends at a tile of 8 rows
_IN_FLIGHT = 16  # copies in flight


def rows_tile(n: int) -> Optional[int]:
    """Rows a grid step over ``n`` rows: the largest of 256, 128, … 8 that
    divides ``n``, or None where none does (the caller keeps its ``jax.numpy``
    form)."""
    return next((t for t in (_TILE, 128, 64, 32, 16, 8) if n % t == 0), None)


def rows_refused(tokens: int, k: int, width: int, dtype) -> Optional[str]:
    """None where the kernels take a layer of ``tokens`` tokens, ``k`` choices
    and rows of ``width`` in ``dtype``, else why they do not: the rows are whole
    128-lane registers of float32 or bfloat16, the tokens whole tiles (so the
    tables are whole groups of eight rows), and a pair's position fits the
    scalar core's 32-bit word."""
    if jnp.dtype(dtype) not in _DTYPES:
        return f"rows of {jnp.dtype(dtype).name} are neither float32 nor bfloat16"
    if width % 128:
        return f"width {width} is no multiple of 128"
    if rows_tile(tokens) is None:
        return f"{tokens} tokens are no multiple of 8"
    if tokens * k >= 2**31:
        return f"{tokens * k} pairs do not fit an int32"
    return None


def _row_of(slots, slot, at):
    """Row ``at`` of the group in ``slots[slot]``, ``[1, d]`` float32."""
    sub = at % _GROUP
    if slots.dtype != jnp.bfloat16:
        return slots[slot, pl.ds(sub, 1), :]
    words = slots.bitcast(_U32)[slot, pl.ds(sub >> 1, 1), :]
    return jax.lax.bitcast_convert_type(jnp.where((sub & 1) == 1, words & _U32(0xFFFF0000), words << 16), _F32)


def _ring(count, at_of, table_ref, slots, sems, use, first=0):
    """``use(p, row)`` for ``p`` in ``[first, first + count)`` in turn, ``row``
    the float32 ``[1, d]`` row ``at_of(p)`` of the table, its group fetched
    ``_IN_FLIGHT`` copies ahead."""

    def copy(p, at):
        slot = (p - first) % _IN_FLIGHT
        base = pl.multiple_of((at // _GROUP) * _GROUP, _GROUP)
        return pltpu.make_async_copy(table_ref.at[pl.ds(base, _GROUP)], slots.at[slot], sems.at[slot])

    def start(p, carry):
        copy(p, at_of(p)).start()
        return carry

    def step(p, carry):
        at = at_of(p)
        copy(p, at).wait()
        use(p, _row_of(slots, (p - first) % _IN_FLIGHT, at))

        @pl.when(p + _IN_FLIGHT < first + count)
        def _():
            start(p + _IN_FLIGHT, None)

        return carry

    jax.lax.fori_loop(first, first + jnp.minimum(count, _IN_FLIGHT), start, 0)
    jax.lax.fori_loop(first, first + count, step, 0)


def _slots(table, d):
    """The ring's scratch: room for the groups in flight and a semaphore each."""
    if jnp.dtype(table.dtype) not in _DTYPES:
        raise ValueError(f"rows of {jnp.dtype(table.dtype).name}: float32, or bfloat16 (which widens to float32 by a shift)")
    return [pltpu.VMEM((_IN_FLIGHT, _GROUP, d), table.dtype), pltpu.SemaphoreType.DMA((_IN_FLIGHT,))]


def _compiler_params(tile, d):
    # a tile of float32 rows a few times over: the fetched rows, an input and an output block twice each, a step's temporaries
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=min(100 * 2**20, 16 * tile * d * 4 + 8 * 2**20))


# ---------------------------------------------------------------------------
# the buffer's side
# ---------------------------------------------------------------------------


def _from_table_kernel(meta_ref, src_ref, *refs, tile, k, scaled, dotted, filled):
    refs = list(refs)
    w_ref = refs.pop(0) if scaled else None  # prefetched with ``meta`` and ``src``
    table_ref = refs.pop(0)
    with_ref = refs.pop(0) if dotted else None
    if filled:
        refs.pop(0)  # aliased to the output: what the unwritten rows hold, never read here
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    slots, sems, got = refs[:3]
    column = refs[3] if scaled or dotted else None  # a number a row, spread over a register's lanes
    i = pl.program_id(0)
    n_live, first = meta_ref[0], i * tile

    if dotted:
        @pl.when(i == 0)
        def _():
            dots_ref[...] = jnp.zeros(dots_ref.shape, _F32)

    @pl.when(jnp.logical_or(first < n_live, i == 0))
    def _():
        count = jnp.clip(n_live - first, 0, tile)

        def keep(r, row):
            got[pl.ds(r, 1), :] = row
            if scaled:
                column[pl.ds(r, 1), :] = jnp.full((1, 128), w_ref[src_ref[first + r]], _F32)

        _ring(count, lambda r: src_ref[first + r] // k, table_ref, slots, sems, keep)
        here = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < count  # past it ``got`` holds an earlier tile's rows, or nothing
        rows = got[...]
        scale = column[:, :1] if scaled else None
        if dotted:
            dots = jnp.sum(jnp.where(here, with_ref[...].astype(_F32) * rows, 0.0), axis=1, keepdims=True)
            column[...] = jnp.broadcast_to(dots, (tile, 128))
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

            def place(r, carry):  # a row's product to its pair's place among the tokens' ``[N, k]``, 128 pairs a register row
                q = src_ref[first + r]
                at = pl.ds(q // 128, 1)
                dots_ref[at, :] = jnp.where(lane == q % 128, column[pl.ds(r, 1), :], dots_ref[at, :])
                return carry

            jax.lax.fori_loop(0, count, place, 0)
        if scaled:
            rows = scale * rows
        out_ref[...] = jnp.where(here, rows, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "out_dtype", "tile", "interpret"))
def rows_from_table(table, src, n_live, weights=None, dot_with=None, fill=None, *, k, out_dtype=None, tile=None,
                    interpret: Optional[bool] = None):
    """``out[r] = weights[pair] · table[pair // k]`` with ``pair = src[r]`` (a
    row's pair ``t·k + j``: ``held_rows``' ``src``) for ``r < n_live``, rounded to
    ``out_dtype`` (the table's if None) from float32; zeros from ``n_live`` to
    the end of its tile; **unwritten past that tile** (``fill``, ``[R, d]`` of
    ``out_dtype``, is what those rows then hold; without it whatever the
    allocation held).

    ``table``: ``[N, d]`` float32 or bfloat16, ``N`` a multiple of 8; ``src``:
    ``[R]`` int32; ``n_live``: int32 scalar; ``weights``: ``[N, k]`` float32 or
    None (ones). ``dot_with`` (``[R, d]``): also return ``[N, k]`` float32 with
    ``⟨dot_with[r], table[pair // k]⟩`` (without the weight) at each live row's
    pair and zeros at every other pair."""
    (n, d), (r,) = table.shape, src.shape
    tile = tile or rows_tile(r)
    out_dtype = jnp.dtype(out_dtype or table.dtype)
    scaled, dotted = weights is not None, dot_with is not None
    if n % _GROUP or r % tile:
        raise ValueError(f"a table of {n} rows is not whole groups of {_GROUP}, or {r} rows are not whole tiles of {tile}")
    meta = jnp.stack([n_live, jnp.maximum((n_live + tile - 1) // tile, 1) - 1]).astype(jnp.int32)  # the last live tile
    rows_spec = pl.BlockSpec((tile, d), lambda i, meta, *_: (jnp.minimum(i, meta[1]), 0))
    prefetched = [meta, src.astype(jnp.int32)] + ([weights.astype(_F32).reshape(-1)] if scaled else [])
    operands, in_specs = [table], [pl.BlockSpec(memory_space=pl.ANY)]
    if dotted:
        operands.append(dot_with)
        in_specs.append(rows_spec)
    aliases = {}
    if fill is not None:
        aliases = {len(prefetched) + len(operands): 0}
        operands.append(fill.astype(out_dtype))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_shape, out_specs = [jax.ShapeDtypeStruct((r, d), out_dtype)], [rows_spec]
    registers = -(-n * k // 128)
    if dotted:  # the pairs' products, whole in VMEM from the first grid step to the last
        out_shape.append(jax.ShapeDtypeStruct((registers, 128), _F32))
        out_specs.append(pl.BlockSpec((registers, 128), lambda i, *_: (0, 0)))
    scratch = _slots(table, d) + [pltpu.VMEM((tile, d), _F32)] + ([pltpu.VMEM((tile, 128), _F32)] if scaled or dotted else [])
    out = pl.pallas_call(
        functools.partial(_from_table_kernel, tile=tile, k=k, scaled=scaled, dotted=dotted, filled=fill is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=len(prefetched), grid=(r // tile,), in_specs=in_specs,
                                               out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_compiler_params(tile, d),
        interpret=resolve_interpret(interpret),
        name="moe_rows_from_table",
    )(*prefetched, *operands)
    return (out[0], out[1].reshape(-1)[:n * k].reshape(n, k)) if dotted else out[0]


# ---------------------------------------------------------------------------
# the tokens' side
# ---------------------------------------------------------------------------


def live_pairs(live, tile: int):
    """The live pairs as one token-major list, for :func:`tokens_from_rows`:
    ``(starts, pair)``. ``pair[p]`` is the ``p``-th live pair (``t·k + j``,
    ascending; past the live ones ``N·k``) and ``starts[i]`` the list position
    of the first pair of token tile ``i`` (``starts[-1]``: the live count). A
    sort of the pairs' numbers with the dead ones sent to the end: on the chip
    0.25 ms for 98,304 pairs, where a ``cumsum`` and a scatter took 0.56."""
    n, k = live.shape
    pair = jnp.sort(jnp.where(live.reshape(-1), jnp.arange(n * k, dtype=jnp.int32), n * k))
    counts = jnp.sum(live.reshape(n // tile, tile * k).astype(jnp.int32), axis=1)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]), pair


def _from_rows_kernel(starts_ref, pair_ref, dest_ref, *refs, tile, k, weighted):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, slots, sems = refs[:4]
    acc = refs[4] if len(refs) > 4 else out_ref  # float32 sums: the output itself where it is float32
    i = pl.program_id(0)
    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def inside(p):
        return pair_ref[p] - i * (tile * k)  # the pair inside this tile: token q // k, choice q % k

    def add(p, row):
        q = inside(p)
        if weighted:
            row = w_ref[q] * row
        t = q // k
        acc[pl.ds(t, 1), :] = acc[pl.ds(t, 1), :] + row

    _ring(starts_ref[i + 1] - starts_ref[i], lambda p: dest_ref[inside(p)], rows_ref, slots, sems, add, first=starts_ref[i])
    if acc is not out_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tile", "interpret"))
def tokens_from_rows(rows, starts, pair, dest, weights=None, *, tile, out_dtype=None, interpret: Optional[bool] = None):
    """``out[t] = Σ_j live[t, j] · weights[t, j] · rows[dest[t, j]]`` (``weights``
    None: ones), ``[N, d]``, summed in float32 in the order ``j = 0 … k−1`` and
    rounded to ``out_dtype`` (float32 if None) once at the end.

    ``rows``: ``[R, d]`` float32 or bfloat16, ``R`` a multiple of 8; ``starts``,
    ``pair``: :func:`live_pairs` of the routing at ``tile`` tokens a grid step;
    ``dest``: ``[N, k]`` int32, a pair's row of the buffer (read for live pairs
    only); ``weights``: ``[N, k]`` float32."""
    r, d = rows.shape
    n, k = dest.shape
    tiles = n // tile
    out_dtype = jnp.dtype(out_dtype or _F32)
    weighted = weights is not None
    if r % _GROUP or n % tile or starts.shape != (tiles + 1,):
        raise ValueError(f"a buffer of {r} rows is not whole groups of {_GROUP}, or {n} tokens and {starts.shape[0]} starts are not tiles of {tile}")
    block = -(-tile * k // 1024) * 1024  # the scalar core's memory takes a vector in blocks of 1024 words

    def by_tile(pairs):  # a tile's pairs, one after the other
        return jnp.pad(pairs.reshape(tiles, tile * k), ((0, 0), (0, block - tile * k))).reshape(-1)

    scalars = pl.BlockSpec((block,), lambda i, *_: (i,), memory_space=pltpu.SMEM)
    operands, in_specs = [by_tile(dest.astype(jnp.int32))], [scalars]
    if weighted:
        operands.append(by_tile(weights.astype(_F32)))
        in_specs.append(scalars)
    scratch = _slots(rows, d) + ([] if out_dtype == _F32 else [pltpu.VMEM((tile, d), _F32)])
    return pl.pallas_call(
        functools.partial(_from_rows_kernel, tile=tile, k=k, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2, grid=(tiles,), in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
                                               out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)), scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        compiler_params=_compiler_params(tile, d),
        interpret=resolve_interpret(interpret),
        name="moe_tokens_from_rows",
    )(starts.astype(jnp.int32), pair.astype(jnp.int32), *operands, rows)
