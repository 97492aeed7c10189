"""Pallas TPU kernels: fused flash attention.

The reference gets its fused kernels from cuDNN via torch
(``/root/reference/requirements.txt:12-24``, ``model/vgg16.py:9-14``); the
TPU-native equivalent obligation (SURVEY.md §2b) is custom Pallas kernels
where plain XLA underperforms — attention being the canonical case: a
materialized ``[B, H, T, T]`` score tensor is HBM-bandwidth-bound, while the
flash formulation streams K/V blocks through VMEM with an online softmax and
never materializes the scores.

Public surface:

* :func:`flash_attention` — q / k ``[B, T, H, Dqk]`` and v ``[B, T, H, Dv]``
  -> ``[B, T, H, Dv]``, same contract as ``models.vit.dot_product_attention``
  (scale = Dqk**-0.5, optional causal mask), differentiable (custom VJP, one
  flash backward kernel). ``Dv`` is ``Dqk`` for every model but latent
  attention's (192 against 128), whose v is never padded to the wider side.
* :func:`make_attention_fn` — adapter for ``models.vit.MultiHeadAttention``'s
  ``attention_fn`` hook; picks the kernel on TPU and the plain XLA path
  elsewhere.

Kernel design (see /opt/skills/guides/pallas_guide.md): grid over
``(batch, head, q-block)``; K/V live in VMEM as whole ``[T, D]`` slabs per
(batch, head) — compile-checked for the v5e through T = 8192 at D = 64/128
and at q / k 192 with v 128
(at 16,384 the slabs alone are refused); beyond that, sequence
parallelism (``parallel.ring_attention``) shards T across chips and each shard
re-enters this kernel. Softmax statistics are carried in float32; matmuls run
on the MXU with ``preferred_element_type=float32``. Both kernels hold their
score tiles keys x queries, so the statistics are the ``[1, bq]`` lane-major
rows lse is stored as. The backward pass is the
standard flash decomposition in one kernel: a delta precompute
(``rowsum(dO * O)``, plain XLA), then a grid over ``(batch, head, k-block)``
with q / dO / lse / delta resident as whole-T slabs and a loop over q-blocks
that recomputes the scores of a block pair once and takes dv, dk and the
pair's share of dq from them — five matmuls a pair; dk / dv are carried in
float32 through the loop, dq is summed in a float32 VMEM slab across the
(sequential) k-block grid axis — so the [T, T] score matrix is never
materialized in either direction.

Inside a kernel the loop over the other side's blocks follows the causal
mask: a q-block stops at the last k-block the diagonal reaches
(:func:`_k_blocks_end`), a k-block starts at the first q-block that can see it
(:func:`_q_blocks_start`), so a block whose every weight would be exactly 0 is
never computed; non-causal calls visit every block. Every visited block still
builds the iota / compare / select mask: a second, mask-free loop for the
blocks wholly below the diagonal measured 2-7% *slower* on the v5e (the
mask's vector work hides under the exponentials and matmuls; a second loop's
carried accumulators do not — PERF.md §6, PR 26). The block shape comes from
``(T_q, T_k, causal)``, one shape per kernel (:func:`_flash_blocks`).

A causal call whose score square is ONE block pair (T <= 1024 under that rule)
has no block to skip and no loop: both kernels walk the block as static
sub-tiles instead (:func:`_flash_sub`, :func:`_fwd_subtiles`,
:func:`_bwd_subtiles`) — a group of rows (forward) or of keys (backward) takes
everything the mask lets it see as one tile, and the sub-tiles above the
diagonal are not in the program at all; a row's softmax is whole inside its
tile, so the forward carries and rescales nothing. :func:`flash_block_plan`
reports both kernels' block shapes and sub-tile sizes, with the number of
block pairs (or sub-tiles) the forward visits, for the ``kernel_dispatch``
record.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30  # large-negative logit for masked positions (f32-safe)

# What the forward kernel hands the backward besides q / k / v, under names a
# ``jax.checkpoint`` policy can keep (``models/hybrid_lm.py:REMAT_POLICY``):
# outside a checkpoint ``checkpoint_name`` is an identity that lowers to nothing.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """THE interpret decision, for every ``pl.pallas_call`` in the package
    (the flash kernels here, ``conv1x1_bn_act``, and through
    ``flash_block_fwd/bwd`` the ring path): on a TPU a kernel is always handed
    to the Mosaic compiler; the Pallas interpreter runs only where a caller
    asks for it (``interpret=True`` — parity tests) or where there is no TPU
    to compile for, which is the CPU test rig: the kernel-dispatch policy
    (``ops/dispatch.py``) routes every *auto* selection to the plain XLA path
    off-TPU, so off-TPU interpretation is reached only by a forced knob."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _flash_blocks(kernel: str, t_q: int, t_k: int, causal: bool) -> tuple[int, int]:
    """``(block_q, block_k)`` for one of the kernels ``"fwd"``, ``"bwd"``, from
    the shapes alone; :func:`_block_size` then clamps to T.

    Measured on the TPU v5e (``scripts/flash_block_sweep.py``: bf16, causal,
    each kernel alone over ``{256, 512, 1024}^2``, ms a call; PERF.md §6 has
    the whole grids). The forward rows time the keys x queries kernel with
    ``--kernel-layout`` (operands already in its layout); the backward rows
    also carry the 0.75-1.6 ms a call of layout copies that a one-kernel
    program adds, and keep their winners without them (7.54 at 512 x 512 of
    T=4096, 2.75 as one tile of T=1024, 13.12 at 1024 x 1024 of T=8192):

    =======================  =========  ==========  ===========
    shape                    512 x 512  1024 x 512  1024 x 1024
    =======================  =========  ==========  ===========
    fwd [8,12,4096,64]       3.48       **3.29**    3.39
    bwd [8,12,4096,64]       **8.90**   9.00        9.13
    fwd [32,12,1024,64]      1.35       1.43        1.11
    bwd [32,12,1024,64]      4.24       4.78        **4.21**
    fwd [4,12,8192,64]       6.32       **5.73**    5.90
    bwd [4,12,8192,64]       15.07      14.77       **14.71**
    fwd [2,32,8192,128]      10.65      **9.55**    9.68
    fwd [2,32,8192,192/128]  12.07      **11.93**   12.23
    =======================  =========  ==========  ===========

    A forward block of 1024 query rows streams twice the rows through each
    tile of k^T the MXU holds, and a 256-wide block on either side loses
    everywhere. So a causal forward past T=1024 runs 1024 x 512: it visits 20
    of T=4096's 32 block pairs where 512 x 512 visits 36 of 64 (more masked
    elements, half the loop trips). Latent attention's widths (q / k 192, v
    128) need 18 MiB there, over the compiler's 16 MiB default, and are
    compiled under :func:`_fwd_vmem_bytes` (their last two columns were read
    under it). At T=1024 a causal call is one
    1024 x 1024 block pair whose masked sub-tiles are left out of the program
    (:func:`_flash_sub`: 0.96 ms a call forward, against 1.11 as one tile).
    Non-causal calls have nothing to skip and keep 1024 x 1024 to T=4096 and
    512 x 512 past it. The backward is compiled under a limit of its own
    (:func:`_bwd_vmem_bytes`) and is fastest at 512 x 512 to T=4096 and at
    1024 x 1024 past it. At 16,384 the forward's slabs alone are refused."""
    t = max(t_q, t_k)
    if kernel == "fwd" and causal and t > 1024:
        return 1024, 512
    if t > 4096:
        return (1024, 1024) if kernel == "bwd" else (512, 512)
    if causal and t > 1024:
        return 512, 512
    return 1024, 1024


def _block_size(block: int, t: int) -> int:
    """Clamp a block size to the sequence, rounded up to the MXU tile.

    A raw ``min(block, t)`` leaves ragged blocks at short T (ViT-B's 197),
    and a 197-wide tile maps terribly onto the 128-lane MXU / (8,128) VMEM
    tiling. Padded rows/cols are
    masked by ``seq_len`` inside the kernels (K side) or sliced off by the
    callers (q side), so alignment costs only the pad FLOPs.
    """
    if t >= block:
        return block
    return min(block, ((max(t, 1) + 127) // 128) * 128)


# Groups a side that a one-block causal call is walked in, each kernel's
# preference first (:func:`_flash_sub` has the sweep they come from).
_SUB_SPLITS = {"fwd": (2, 4), "bwd": (8, 4, 2)}


def _flash_sub(kernel: str, t_q: int, t_k: int, bq: int, bk: int, causal: bool) -> Optional[int]:
    """The sub-tile size a kernel (``"fwd"``, ``"bwd"``) walks its one block
    pair in, from what the call observes, or None for the one-tile body.

    A causal call whose score square is one block pair (every causal call
    with T <= 1024 under :func:`_flash_blocks`) has nothing to skip at block
    granularity and, as one tile, computes the masked triangle only to mask
    it: twice the required work. Split into ``n`` groups of ``sub`` rows and
    columns, the ``n (n - 1) / 2`` sub-tiles above the diagonal are left out
    of the program statically — no loop, no carried accumulator — and each
    group takes its whole visible range as one tile, so the forward needs no
    online rescale either: 3/4 of the square is executed at ``n = 2``, 5/8 at
    ``n = 4``, 9/16 at ``n = 8``. ``sub`` is the larger side over ``n`` for
    the first ``n`` of the kernel's preference (``_SUB_SPLITS``) that gives a
    multiple of the 128-lane tile dividing both sides (``T_q != T_k`` occurs
    on the ring's diagonal block).
    None — today's body, bit for bit — for non-causal calls, for any axis of
    several blocks, and where the block does not split so (T <= 128; 256
    against 384).

    Measured on the TPU v5e (``scripts/flash_block_sweep.py --sub
    512,256,128``: bf16, ``[32, 12, 1024, 64]``, causal, each kernel alone at
    1024 x 1024, ms a call; PR 32; PERF.md §6 has the variants tried):

    =======================  ========  =========  =========  =========
    kernel                   one tile  sub = 512  sub = 256  sub = 128
    =======================  ========  =========  =========  =========
    fwd alone                1.98      **1.72**   1.81       1.92
    fwd ``--kernel-layout``  1.22      **0.94**   1.03       1.14
    bwd alone                4.21      3.55       3.27       **3.18**
    bwd ``--kernel-layout``  2.75      2.11       1.81       **1.67**
    =======================  ========  =========  =========  =========

    (Alone, a call also transposes its operands between XLA's entry layout
    and the kernel's, 0.8 / 1.5 ms whatever the sub-tile; ``--kernel-layout``
    pins them. In ``gpt2s_t1024``'s traced step a call reads 1.17 -> 0.90 ms
    forward and 2.70 -> 1.62 backward, and the other splits rank as here.)

    The backward keeps gaining down to the lane tile (its five matmuls a
    tile, each half-filling an MXU pass at head dim 64, are most of its time,
    and fewer sub-tiles are fewer matmul rows); the forward is best at two
    row groups: past that its smaller matmuls lose what the skipped
    sub-tiles save."""
    if not causal or t_q > bq or t_k > bk:
        return None
    for n in _SUB_SPLITS[kernel]:
        sub = max(bq, bk) // n
        if sub % 128 == 0 and bq % sub == 0 and bk % sub == 0:
            return sub
    return None


def _resolve_blocks(kernel, block_q, block_k, t_q, t_k, causal) -> tuple[int, int, Optional[int]]:
    """The ``(bq, bk, sub)`` one kernel (``"fwd"``, ``"bwd"``) runs at: a
    caller's explicit size wins, else the shape rule's; both clamped to T;
    ``sub`` as :func:`_flash_sub` gives it for those blocks."""
    rule_q, rule_k = _flash_blocks(kernel, t_q, t_k, causal)
    bq, bk = _block_size(block_q or rule_q, t_q), _block_size(block_k or rule_k, t_k)
    return bq, bk, _flash_sub(kernel, t_q, t_k, bq, bk, causal)


def flash_block_plan(t_q, t_k, causal, block_q=None, block_k=None) -> dict:
    """What the flash path does at these shapes, for the ``kernel_dispatch``
    record: the forward's block shape, the sub-tile size it walks a one-block
    causal call in (None: one tile) and :func:`flash_block_counts` at the
    granularity it skips at, which forward body runs (``fwd_tiles``:
    ``"sub"``, :func:`_fwd_subtiles`; ``"kq"``, :func:`_fwd_kernel`'s keys x
    queries tiles), and which backward the custom VJP takes (``"fused"``:
    dq, dk and dv from one kernel, the only backward there is) at which block
    shape and sub-tile size."""
    bq, bk, sub = _resolve_blocks("fwd", block_q, block_k, t_q, t_k, causal)
    total, computed = flash_block_counts(t_q, t_k, sub or bq, sub or bk, causal)
    bwd_q, bwd_k, bwd_sub = _resolve_blocks("bwd", block_q, block_k, t_q, t_k, causal)
    return {
        "block_q": bq, "block_k": bk, "sub_block": sub, "fwd_tiles": "sub" if sub else "kq",
        "blocks_total": total, "blocks_computed": computed,
        "backward": "fused", "bwd_block_q": bwd_q, "bwd_block_k": bwd_k, "bwd_sub_block": bwd_sub,
    }


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit(x: jax.Array, size: int, axis: int) -> jax.Array:
    """Zero-pad ``x`` to ``size`` along ``axis``, or drop zero padding down to it."""
    if x.shape[axis] > size:
        return jax.lax.slice_in_dim(x, 0, size, axis=axis)
    return _pad_to(x, size, axis)


# ---------------------------------------------------------------------------
# Which blocks a kernel visits
# ---------------------------------------------------------------------------
#
# Positions count from 0 on both sides inside a call (row r sees column c iff
# r >= c), also where Tq != Tk (flash_block_fwd/bwd). q-block i holds rows
# [i*bq, (i+1)*bq), k-block j columns [j*bk, (j+1)*bk): the pair (i, j) has an
# unmasked element iff j*bk < (i+1)*bq. The two bounds below say that from
# either side; they take Python ints (flash_block_counts) and traced int32
# scalars (the kernels, from pl.program_id) alike, so the record and the
# kernels share one formula.


def _k_blocks_end(qi, bq: int, bk: int, n_k: int):
    """q-block ``qi`` sees k-blocks ``[0, end)`` under the causal mask."""
    end = ((qi + 1) * bq + bk - 1) // bk
    return min(n_k, end) if isinstance(end, int) else jnp.minimum(n_k, end)


def _q_blocks_start(ki, bq: int, bk: int):
    """k-block ``ki`` is seen by q-blocks ``[start, n_q)`` under the causal
    mask (``start`` may lie past ``n_q`` where Tq < Tk: seen by none)."""
    return (ki * bk) // bq


def flash_block_counts(t_q: int, t_k: int, bq: int, bk: int, causal: bool) -> tuple[int, int]:
    """``(blocks_total, blocks_computed)``: the ``[bq, bk]`` block pairs of one
    (batch, head)'s ``T_q x T_k`` score square, and how many of them the
    forward kernel visits (the backward kernel visits the same pairs at its
    own block shape). Sub-tiles of one block pair (:func:`_flash_sub`) count
    the same way, at ``bq = bk = sub``: the kernels take their static bounds
    from the same two functions."""
    n_q, n_k = pl.cdiv(t_q, bq), pl.cdiv(t_k, bk)
    total = n_q * n_k
    if not causal:
        return total, total
    return total, sum(_k_blocks_end(qi, bq, bk, n_k) for qi in range(n_q))


def _grid_index(axis: int, extent: int):
    """``pl.program_id(axis)``, or a plain 0 on an axis of one block: the
    bounds above then fold to Python ints and the kernels' loops to static
    ones, so a call whose T fits one block (ViT's 197; a causal block that
    :func:`_flash_sub` does not split) lowers to the straight-line program it
    was before the loops followed the mask (a dynamic trip count of 1 measured
    +35% on the forward at T=1024)."""
    return pl.program_id(axis) if extent > 1 else 0


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k, seq_len, causal, n_q):
    """One q-block against the k-blocks it can see, online softmax. Refs are
    (1, 1, bq, Dqk) / (1, 1, Tp, Dqk) blocks, v and o ``Dv`` wide; statistics
    in f32. (A causal call of one block pair runs :func:`_fwd_subtiles`
    instead.)

    The tiles are held as :func:`_bwd_kernel` holds them, keys x queries
    (``s^T = k q^T``, ``[bk, bq]``): the running maximum and sum are then
    ``[1, bq]`` lane-major rows, reduced over the tile's sublanes, and the
    accumulator is ``o^T`` (``[Dv, bq]``, ``+= v^T p^T``), rescaled by a
    sublane broadcast of the ``[1, bq]`` correction and transposed once after
    the loop. (The other way up — ``[bq, bk]`` tiles, ``[bq, 1]`` statistics
    reduced across lanes and broadcast across them to rescale ``[bq, Dv]`` —
    carries 192 vregs a trip where this carries 40 at ``bq`` = 512 and width
    64: 4.28 ms a call of ``[8, 12, 4096, 64]`` at 512 x 512 against 3.48,
    PERF.md §6.)"""
    bq = q_ref.shape[2]
    d = v_ref.shape[3]  # the accumulator is o's width
    t_pad = k_ref.shape[2]
    n_k = t_pad // block_k
    padded = t_pad > seq_len  # only then does a key need hiding for its position
    qi = _grid_index(2, n_q)

    # Matmuls run in the input dtype (bf16 in production — one MXU pass; an
    # f32 cast would force the 3x-slower f32 path) with f32 accumulation;
    # softmax statistics and the scale multiply stay f32.
    q = q_ref[0, 0]  # [bq, D]
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (block_k, bq), 1)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]  # [bk, Dqk]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]  # [bk, Dv]
        st = scale * jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, bq] f32
        if padded or causal:
            k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, bq), 0)
            mask = k_idx < seq_len if padded else None
            if causal:
                seen = q_idx >= k_idx
                mask = seen if mask is None else jnp.logical_and(mask, seen)
            st = jnp.where(mask, st, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))  # [1, bq]
        pt = jnp.exp(st - m_new)  # [bk, bq]
        alpha = jnp.exp(m - m_new)  # [1, bq]
        l_new = l * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            v, pt.astype(v.dtype), (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Dv, bq]
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((d, bq), jnp.float32)
    m0 = jnp.full((1, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, bq), jnp.float32)
    # Causal: stop at the last k-block the diagonal reaches — every weight
    # past it is exactly 0. Ascending j, so the first block visited holds
    # column 0, which every row sees: m is a real logit before a wholly
    # masked row of a tile meets it.
    end = _k_blocks_end(qi, bq, block_k, n_k) if causal else n_k
    acc, m, l = jax.lax.fori_loop(0, end, body, (acc0, m0, l0))
    # Padded q rows (and fully-masked causal rows cannot occur: row i always
    # sees k=i) have l=0 only when the whole row was padding; guard the divide.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = jnp.transpose(acc / l_safe, (1, 0)).astype(o_ref.dtype)  # [bq, Dv]
    # Stats live as [1, bq] lane-major rows: a [B, H, 1, T] buffer pads only
    # its singleton sublane dim (8x on 1), where a [..., T, 1] layout would
    # pad the lane dim 128x (measured 384MB/layer on ViT-B — OOM).
    lse_ref[0, 0] = m + jnp.log(l_safe)  # [1, bq]


def _hide(s, q0: int, k0: int, seq_len: int, keys_on_rows: bool = False):
    """A score tile whose first query is position ``q0`` and first key ``k0``,
    with NEG_INF where the causal mask or the key padding hides a key; each
    compare is built only where the tile's place says some element needs it
    (a sub-tile below the diagonal and short of the padding comes back as it
    is). ``keys_on_rows``: the tile is ``[keys, queries]``, else
    ``[queries, keys]``."""
    n_k, n_q = s.shape if keys_on_rows else s.shape[::-1]
    k_axis = 0 if keys_on_rows else 1
    causal = k0 + n_k - 1 > q0  # the last key is past the first query
    padded = k0 + n_k > seq_len
    if not (causal or padded):
        return s
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    mask = k_idx < seq_len if padded else None
    if causal:
        seen = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - k_axis) >= k_idx
        mask = seen if mask is None else jnp.logical_and(mask, seen)
    return jnp.where(mask, s, NEG_INF)


def _fwd_subtiles(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, sub, seq_len):
    """The forward of a causal call whose score square is one block pair, as
    static sub-tiles: row group ``r`` (``sub`` rows) takes the columns it can
    see, ``[0, _k_blocks_end(r) * sub)``, as ONE ``[sub, width]`` tile — one
    score product, one max, one exponential pass, one sum, one ``p @ v``, one
    divide. The sub-tiles above the diagonal are never built (every weight
    there is exactly 0), and a row's softmax is whole inside its tile: no
    loop, no carried ``acc / m / l``, no rescale. The mask is laid on the
    group's diagonal sub-tile alone (and on padded keys where there are any):
    the columns before it are seen by every row of the group."""
    bq = q_ref.shape[2]
    n_k = k_ref.shape[2] // sub
    for r in range(bq // sub):
        rows = pl.ds(r * sub, sub)
        width = _k_blocks_end(r, sub, sub, n_k) * sub
        below = min(r * sub, width)  # columns every row of the group sees
        q = q_ref[0, 0, rows, :]  # [sub, D]
        k = k_ref[0, 0, pl.ds(0, width), :]  # [width, D]
        v = v_ref[0, 0, pl.ds(0, width), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, width] f32
        parts = [
            _hide(s[:, k0:end], r * sub, k0, seq_len)
            for k0, end in ((0, below), (below, width)) if end > k0
        ]
        # Column 0 is seen by every row: m is a real logit and l >= 1.
        m = functools.reduce(jnp.maximum, [jnp.max(x, axis=1, keepdims=True) for x in parts])
        parts = [jnp.exp(x - m) for x in parts]
        l = functools.reduce(jnp.add, [jnp.sum(x, axis=1, keepdims=True) for x in parts])
        p = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, D]
        o_ref[0, 0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, rows] = jnp.transpose(m + jnp.log(l), (1, 0))  # [1, sub]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *scratch,
    scale, block_q, seq_len, causal, n_k,
):
    """dq, dk and dv for one k-block, looping over the q-blocks that can see it;
    s, the mask, p and dp are computed once a block pair and feed all three:
    dv_j = sum_i p_ij^T do_i ; dk_j = scale * sum_i ds_ij^T q_i ;
    dq_i = scale * sum_j ds_ij k_j, with ds_ij = p_ij * (dp_ij - delta_i).

    The tiles are held transposed, ``[bk, bq]`` (s^T = k q^T, dp^T = v dO^T):
    the statistics are then the ``[1, bq]`` lane-major rows they are stored
    as, dv and dk are plain products, and only dq's contracts a tile over its
    rows. (The other way up — ``[bq, bk]`` tiles, as the two kernels this one
    replaced held them — transposes lse and delta every pair and a tile in two
    of the three products: 6-13% slower at T=4096, PERF.md §6, PR 30.)

    dq's sum runs over the grid's k-block axis, which is therefore sequential:
    a float32 ``[T_q, D]`` scratch slab is zeroed at k-block 0, added to by
    rows inside the loop, and scaled, cast and written to the resident dq
    block once, after the last k-block (ascending j, every addition float32).
    Where the axis has one block there is nothing to sum and no scratch: the
    rows go straight to ``dq_ref``. (A causal call of one block pair runs
    :func:`_bwd_subtiles` instead.)"""
    bk = k_ref.shape[2]
    d, dv_width = k_ref.shape[3], v_ref.shape[3]
    t_pad = q_ref.shape[2]
    n_q = t_pad // block_q
    ki = _grid_index(2, n_k)

    k = k_ref[0, 0]  # [bk, D]
    v = v_ref[0, 0]
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)
    if n_k > 1:
        (dq_acc,) = scratch

        @pl.when(ki == 0)
        def _():
            dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(i, carry):
        dk, dv = carry
        rows = pl.ds(i * block_q, block_q)
        q = q_ref[0, 0, rows, :]  # [bq, D]
        do = do_ref[0, 0, rows, :]
        lse = lse_ref[0, 0, :, rows]  # [1, bq]
        delta = delta_ref[0, 0, :, rows]
        st = scale * jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, bq] f32
        q_idx = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 1)
        mask = k_idx < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_idx >= k_idx)
        st = jnp.where(mask, st, NEG_INF)
        # [bk, bq]. Padded q rows (zero q, zero-padded lse) give s=0, lse=0,
        # p=1 — NOT p=0. Their contributions still vanish only because dO and
        # delta are zero-padded (dv += p^T·dO = 0; ds = p*(dp-delta) has
        # dp = dO·v^T = 0 and delta = 0). Keep the dO/delta zero-padding.
        pt = jnp.exp(st - lse)
        dv_new = dv + jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, bq]
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_new = dk + jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dq = jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, D]: this pair's share of dq's rows
        if n_k > 1:
            dq_acc[rows, :] += dq
        else:
            dq_ref[0, 0, rows, :] = (dq * scale).astype(dq_ref.dtype)
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, dv_width), jnp.float32)
    # Causal: start at the first q-block the diagonal lets see this k-block.
    start = _q_blocks_start(ki, block_q, bk) if causal else 0
    dk, dv = jax.lax.fori_loop(start, n_q, body, (dk0, dv0))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    if n_k > 1:

        @pl.when(ki == n_k - 1)
        def _():
            dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_subtiles(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *, scale, sub, seq_len
):
    """The backward of a causal call whose score square is one block pair, as
    static sub-tiles in :func:`_bwd_kernel`'s orientation: k row group ``j``
    (``sub`` rows of k / v) against the q columns that can see it, from
    ``_q_blocks_start(j) * sub`` on, as ONE ``[sub, width]`` tile. ``dk_j`` and
    ``dv_j`` come whole from it; the tile's share of dq is added, float32, to
    the row groups it covers (ascending ``j``, as the slab's sum runs), and
    each group of dq is scaled, cast and stored once. A k group no row sees
    (``T_q < T_k``) gets zeros and leaves dq closed."""
    bq, d, dv_width = q_ref.shape[2], q_ref.shape[3], v_ref.shape[3]
    n_q = bq // sub
    dq = [None] * n_q  # float32 [sub, Dqk] a q row group
    for j in range(k_ref.shape[2] // sub):
        krows = pl.ds(j * sub, sub)
        first = min(_q_blocks_start(j, sub, sub), n_q)
        if first == n_q:
            dk_ref[0, 0, krows, :] = jnp.zeros((sub, d), dk_ref.dtype)
            dv_ref[0, 0, krows, :] = jnp.zeros((sub, dv_width), dv_ref.dtype)
            continue
        width = bq - first * sub
        cols = pl.ds(first * sub, width)
        k = k_ref[0, 0, krows, :]  # [sub, D]
        v = v_ref[0, 0, krows, :]
        q = q_ref[0, 0, cols, :]  # [width, D]
        do = do_ref[0, 0, cols, :]
        lse = lse_ref[0, 0, :, cols]  # [1, width]
        delta = delta_ref[0, 0, :, cols]
        # Both score-shaped products before the exponentials: with dp^T after
        # them, as _bwd_kernel's loop body has it, the kernel read 3% slower
        # in the step at sub = 128 (PERF.md §6, PR 32).
        st = scale * jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, width] f32
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, width]
        st = _hide(st, first * sub, j * sub, seq_len, keys_on_rows=True)
        # Padded q rows give p = 1, and vanish through the zero-padded dO and
        # delta, as in _bwd_kernel.
        pt = jnp.exp(st - lse)
        dv = jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, D]
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk = jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [sub, D]
        share = jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [width, D]: this k group's share of dq's rows from `first` on
        dk_ref[0, 0, krows, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, krows, :] = dv.astype(dv_ref.dtype)
        for i in range(first, n_q):
            part = share[(i - first) * sub:(i - first + 1) * sub]
            dq[i] = part if dq[i] is None else dq[i] + part
    for i, rows in enumerate(dq):  # k group 0 is seen by every row: none is None
        dq_ref[0, 0, pl.ds(i * sub, sub), :] = (rows * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Wrapper with custom VJP
# ---------------------------------------------------------------------------


def _to_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))  # [B,T,H,D] -> [B,H,T,D]


def _from_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd_call(qt, kt, vt, t_k, causal, bq, bk, sub, interpret):
    """Forward pallas call on padded [B, H, T*, D] operands (v and o at their
    own width) -> (o, lse) in the padded layout. Shared by flash_attention
    (square T) and the ring block path (Tq from the resident shard, Tk from
    the visiting block).

    Jitted (as :func:`_bwd_call` is) so that a program traces and lowers a
    kernel body once, however many layers and chained steps call it at the
    same shapes: the sub-tile bodies are straight-line code (36 tiles in the
    backward at T=1024), and lowered anew at each of a GPT-2 step program's 72
    call sites they cost the benchmark 7 s of ``setup_s`` (PERF.md §6, PR 32).
    XLA inlines the calls: the compiled program is the same.

    Each kernel of this file is called under a stable ``name=`` inside a
    ``jax.named_scope`` of the same name (``flash_fwd``, ``flash_dqkv``,
    ``conv1x1_bn_act``): the kernel's Python name reaches no device trace on
    today's runtime, and a reader of one matches these (the backward's holds
    ``flash_dq``, which the benchmark's ``flash_bwd_time_share`` looks for)."""
    b, h, tq_pad, d = qt.shape
    tk_pad, dv = kt.shape[2], vt.shape[3]
    if sub is not None:  # causal, one block pair (_flash_sub)
        kernel = functools.partial(_fwd_subtiles, scale=d**-0.5, sub=sub, seq_len=t_k)
        params = None
    else:
        kernel = functools.partial(
            _fwd_kernel, scale=d**-0.5, block_k=bk, seq_len=t_k, causal=causal, n_q=tq_pad // bq
        )
        params = pltpu.CompilerParams(
            vmem_limit_bytes=_fwd_vmem_bytes(tk_pad, bq, bk, d, dv, qt.dtype.itemsize)
        )
    call = pl.pallas_call(
        kernel,
        grid=(b, h, tq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, tk_pad, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, tk_pad, dv), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_pad, dv), qt.dtype),
            jax.ShapeDtypeStruct((b, h, 1, tq_pad), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        return call(qt, kt, vt)


def _fwd_vmem_bytes(tk_pad: int, bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """The scoped-VMEM limit :func:`_fwd_kernel` is compiled under, reckoned
    from its shapes as :func:`_bwd_vmem_bytes` reckons the backward's: the
    whole-T k / v slabs and the q / o blocks double-buffered and padded to the
    128-lane tile, the lse row on 8 sublanes, four float32 ``[bk, bq]`` tiles
    (s, p, the exponential's operand and p's cast) and the ``[Dv, bq]``
    accumulator with its transpose. Latent attention's widths at ``1024 x 512``
    and T=8192 need 18 MiB by Mosaic's own count (binary search on the limit,
    compiled for a described v5e), over the compiler's 16 MiB default; every
    other caller fits that default. Never under it: a ceiling, not a
    reservation."""
    lanes, v_lanes = pl.cdiv(d, 128) * 128, pl.cdiv(dv, 128) * 128
    slabs = 2 * tk_pad * (lanes + v_lanes) * itemsize
    blocks = 2 * bq * (lanes + v_lanes) * itemsize + 2 * 8 * bq * 4
    tiles = 4 * bq * bk * 4 + 2 * bq * v_lanes * 4
    return max(16 * 2**20, slabs + blocks + tiles)


def _fwd_impl(q, k, v, causal, block_q, block_k, interpret, valid_len=None):
    b, t, h, d = q.shape
    t_k = t if valid_len is None else valid_len  # kernels mask keys >= t_k
    bq, bk, sub = _resolve_blocks("fwd", block_q, block_k, t, t, causal)
    qt, kt, vt = _pad_bhtd(q, k, v, bq, bk)
    o, lse = _fwd_call(qt, kt, vt, t_k, causal, bq, bk, sub, interpret)
    return o[:, :, :t, :], lse[:, :, :, :t], (qt, kt, vt)


def _bwd_vmem_bytes(tq_pad: int, bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """The scoped-VMEM limit the fused backward is compiled under, reckoned
    from its shapes as an upper bound on what it holds: every pipelined block
    double-buffered and padded to the 128-lane tile, each width ``d`` (q, k
    and their gradients) or ``dv`` (v, dO, dv) in its own lanes (the q / dO /
    dq whole-T slabs, the two statistics rows on 8 sublanes, the k / v / dk /
    dv blocks), dq's float32 scratch slab, and six float32 ``[bq, bk]`` tiles
    for s, p, dp, ds and their cast and transposed copies. Mosaic's own count
    is a half to a third of this (binary search on the limit, compiled for a
    described v5e: 10.2 MiB at T=4096 and 12.2 at 8192 with 1024 x 1024
    tiles and D=64, 18.4 at T=4096 and D=128, where the reckoning gives
    34.5 / 43 / 34.5), so the compiler's 16 MiB default would refuse D=128
    at that tile and nothing else a caller has today. Never under that
    default: the limit is a ceiling, not a reservation."""
    lanes, v_lanes = pl.cdiv(d, 128) * 128, pl.cdiv(dv, 128) * 128
    slabs = 2 * tq_pad * (2 * lanes + v_lanes) * itemsize + tq_pad * lanes * 4 + 2 * 2 * 8 * tq_pad * 4
    blocks = 2 * 2 * bk * (lanes + v_lanes) * itemsize
    tiles = 6 * bq * bk * 4
    return max(16 * 2**20, slabs + blocks + tiles)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _bwd_call(qt, kt, vt, do, lse_p, delta, t_k, causal, bq, bk, sub, interpret):
    """Backward pallas call on padded [B, H, T*, D] operands -> (dq, dk, dv);
    v, ``do`` and dv at v's own width. Padded q rows are harmless because
    ``do``/``delta`` are zero-padded (see _bwd_kernel); ``t_k`` masks padded
    K rows."""
    b, h, tq_pad, d = qt.shape
    tk_pad, dv = kt.shape[2], vt.shape[3]
    n_k = tk_pad // bk
    if sub is not None:  # causal, one block pair (_flash_sub)
        kernel = functools.partial(_bwd_subtiles, scale=d**-0.5, sub=sub, seq_len=t_k)
    else:
        kernel = functools.partial(
            _bwd_kernel, scale=d**-0.5, block_q=bq, seq_len=t_k, causal=causal, n_k=n_k
        )
    slab = pl.BlockSpec((1, 1, tq_pad, d), lambda bi, hi, ki: (bi, hi, 0, 0))
    v_slab = pl.BlockSpec((1, 1, tq_pad, dv), lambda bi, hi, ki: (bi, hi, 0, 0))
    stat = pl.BlockSpec((1, 1, 1, tq_pad), lambda bi, hi, ki: (bi, hi, 0, 0))
    block = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0))
    v_block = pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, ki: (bi, hi, ki, 0))
    call = pl.pallas_call(
        kernel,
        grid=(b, h, n_k),
        in_specs=[slab, block, v_block, v_slab, stat, stat],
        out_specs=[slab, block, v_block],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_pad, d), qt.dtype),
            jax.ShapeDtypeStruct((b, h, tk_pad, d), kt.dtype),
            jax.ShapeDtypeStruct((b, h, tk_pad, dv), vt.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((tq_pad, d), jnp.float32)] if n_k > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_bytes(tq_pad, bq, bk, d, dv, qt.dtype.itemsize),
        ),
        interpret=interpret,
        name="flash_dqkv",
    )
    with jax.named_scope("flash_dqkv"):
        return call(qt, kt, vt, do, lse_p, delta)


def _bwd_impl(operands, t_q, t_k, seq_len, causal, block_q, block_k, interpret):
    """The backward kernel at its own block shape: the ``[B, H, T*, D]``
    q/k/v/dO and ``[B, H, 1, T*]`` lse/delta, zero-padded or not, are fitted
    to whole blocks of it (zeros on dO / delta, as _bwd_kernel needs)
    -> padded (dq, dk, dv)."""
    bq, bk, sub = _resolve_blocks("bwd", block_q, block_k, t_q, t_k, causal)
    tq_pad, tk_pad = pl.cdiv(t_q, bq) * bq, pl.cdiv(t_k, bk) * bk
    qt, kt, vt, do, lse, delta = operands
    return _bwd_call(
        _fit(qt, tq_pad, 2), _fit(kt, tk_pad, 2), _fit(vt, tk_pad, 2),
        _fit(do, tq_pad, 2), _fit(lse, tq_pad, 3), _fit(delta, tq_pad, 3),
        seq_len, causal, bq, bk, sub, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, valid_len):
    o, _, _ = _fwd_impl(q, k, v, causal, block_q, block_k, interpret, valid_len)
    return _from_bhtd(o)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, valid_len):
    """The forward kernel's ``o`` and ``lse`` are named ``FLASH_OUT`` /
    ``FLASH_LSE``: a rematerialising caller whose policy keeps them reads
    them back in the backward pass instead of running this kernel again."""
    o, lse, (qt, kt, vt) = _fwd_impl(
        q, k, v, causal, block_q, block_k, interpret, valid_len
    )
    o, lse = checkpoint_name(o, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return _from_bhtd(o), (qt, kt, vt, o, lse, q.shape)


def _flash_bwd(causal, block_q, block_k, interpret, valid_len, res, g):
    qt, kt, vt, o, lse, q_shape = res
    b, t, h, d = q_shape
    t_k = t if valid_len is None else valid_len

    do = _to_bhtd(g)
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise precompute, plain XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
    # The residuals are padded to the forward's blocks; _bwd_impl refits them.
    operands = (qt, kt, vt, do, lse, delta)
    dq, dk, dv = _bwd_impl(operands, t, t, t_k, causal, block_q, block_k, interpret)

    return (
        _from_bhtd(dq[:, :, :t, :]),
        _from_bhtd(dk[:, :, :t, :]),
        _from_bhtd(dv[:, :, :t, :]),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Block-level entry points for ring attention (parallel.ring_attention)
# ---------------------------------------------------------------------------
#
# The ring path differentiates at the RING level (one custom VJP around the
# whole rotation schedule), so these wrappers are plain functions: the forward
# returns the per-block (normalized o, lse) the online merge consumes, and the
# backward wrapper computes one block's dq / dk / dv contributions given the
# *global* lse/delta of the resident q shard — exactly the flash
# decomposition, applied blockwise across devices. All take/return
# ``[B, T, H, D]`` (lse/delta ``[B, H, T]``).


def _pad_bhtd(q, k, v, bq, bk):
    """``[B, T, H, D]`` q/k/v -> ``[B, H, T*, D]``, zero-padded to whole blocks."""
    tq, tk = q.shape[1], k.shape[1]
    qt = _pad_to(_to_bhtd(q), pl.cdiv(tq, bq) * bq, 2)
    kt = _pad_to(_to_bhtd(k), pl.cdiv(tk, bk) * bk, 2)
    vt = _pad_to(_to_bhtd(v), pl.cdiv(tk, bk) * bk, 2)
    return qt, kt, vt


def flash_block_fwd(
    q, k, v, *, causal=False, block_q=None, block_k=None, interpret=None,
):
    """One (q-shard x k/v-block) flash pass -> ``(o, lse)``; o is
    block-normalized, lse = log-sum-exp of this block's logits per q row
    (what the cross-block online merge needs). Not differentiable — the ring
    owns the VJP."""
    interpret = resolve_interpret(interpret)
    tq, tk = q.shape[1], k.shape[1]
    bq, bk, sub = _resolve_blocks("fwd", block_q, block_k, tq, tk, causal)
    qt, kt, vt = _pad_bhtd(q, k, v, bq, bk)
    o, lse = _fwd_call(qt, kt, vt, tk, causal, bq, bk, sub, interpret)
    return _from_bhtd(o[:, :, :tq, :]), lse[:, :, 0, :tq]


def flash_block_bwd(
    q, k, v, do, lse, delta, *, causal=False, block_q=None, block_k=None, interpret=None,
):
    """One block's backward contributions ``(dq, dk, dv)`` given the global
    ``lse``/``delta`` ``[B, H, Tq]`` of the resident q shard."""
    interpret = resolve_interpret(interpret)
    tq, tk = q.shape[1], k.shape[1]
    operands = (*map(_to_bhtd, (q, k, v, do)), lse[:, :, None, :], delta[:, :, None, :])
    dq, dk, dv = _bwd_impl(operands, tq, tk, tk, causal, block_q, block_k, interpret)
    return (
        _from_bhtd(dq[:, :, :tq, :]),
        _from_bhtd(dk[:, :, :tk, :]),
        _from_bhtd(dv[:, :, :tk, :]),
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    valid_len: Optional[int] = None,
) -> jax.Array:
    """Fused flash attention: q and k ``[B, T, H, Dqk]``, v ``[B, T, H, Dv]``,
    output ``[B, T, H, Dv]``. The two widths are equal for every model but
    latent attention's (queries and keys 192 wide, values 128); v keeps its
    own width in both kernels, never padded to q's.

    Numerics match ``models.vit.dot_product_attention`` (softmax statistics in
    float32, scale ``Dqk**-0.5``); memory is O(T) per (batch, head) instead of
    the O(T^2) score tensor. ``block_q`` / ``block_k`` set one block shape for
    both kernels; left ``None``, each kernel takes the shape
    :func:`_flash_blocks` gives it. ``interpret=None`` auto-selects
    (:func:`resolve_interpret`). ``valid_len`` masks key
    positions >= it — for caller-padded sequences (``ViT.pad_seq_to``); the
    kernels' own seq_len masking does the work, no score tensor or bias mask
    is ever built.
    """
    if q.ndim != 4 or v.ndim != 4 or q.shape != k.shape or q.shape[:3] != v.shape[:3]:
        raise ValueError(f"expected q and k [B,T,H,Dqk] and v [B,T,H,Dv] (Dv may differ from Dqk), "
                         f"got {q.shape}/{k.shape}/{v.shape}")
    if valid_len is not None:
        if causal:
            raise ValueError("valid_len composes with non-causal attention only")
        if not 0 < valid_len <= q.shape[1]:
            raise ValueError(f"valid_len {valid_len} out of range for T={q.shape[1]}")
    interpret = resolve_interpret(interpret)

    def kernel(q, k, v):
        return _flash(q, k, v, causal, block_q, block_k, interpret, valid_len)

    spec = _ambient_shard_spec(q.shape)
    if spec is None:
        return kernel(q, k, v)
    return jax.shard_map(
        kernel, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def _ambient_shard_spec(shape):
    """How a ``[B, T, H, D]`` attention operand (or the Mamba-2 scan's ``x``,
    ``ops/ssd.py:ssd_scan``) is split over the ambient mesh (the one
    ``TrainEngine``/``InferEngine`` set around their jits), or None when the
    kernel should be called as is.

    XLA has no partitioning rule for the Mosaic custom call: inside a jit
    over a multi-device mesh jax 0.9 refuses it outright ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — v5e x4, PR 21), and a partitioner that accepted it could only
    gather q/k/v and run the whole global batch on every chip.
    Attention is independent per (batch row, head), so the kernel runs under
    ``shard_map`` instead: batch over the batch-sharded axes (``data`` x
    ``fsdp``), heads over ``tensor`` — each where the extent divides (the
    batch-1 example input of ``model.init`` stays whole). No ambient mesh, a
    one-device mesh, or a caller already inside a manual region (the ring and
    Ulysses paths, ``pipeline_apply``) means no wrapping."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.manual_axes:
        return None
    from distributed_training_pytorch_tpu.parallel.mesh import TENSOR_AXIS, ambient_batch_axes

    b, _, h, _ = shape
    batch_axes, _ = ambient_batch_axes(b)
    heads = mesh.shape.get(TENSOR_AXIS, 1)
    head_axis = TENSOR_AXIS if heads > 1 and h % heads == 0 else None
    if not batch_axes and head_axis is None:
        return None
    return P(batch_axes or None, None, head_axis, None)


# Below this sequence length the plain O(T^2) XLA path wins: the score tensor
# is small enough to live in VMEM-friendly fusions, while the kernel pays
# layout transposes + block padding. The crossover is not measured on
# today's chip (ROADMAP S2(c)); the plain path's [B,H,T,T] score tensor
# grows as T^2 and cannot fit at T=8192 beyond a tiny batch.
FLASH_MIN_SEQ_LEN = 512


def make_attention_fn(causal: bool = False, min_seq_len: int = FLASH_MIN_SEQ_LEN, **kwargs):
    """Adapter for ``MultiHeadAttention(attention_fn=...)`` (models/vit.py).

    Shape-aware: dispatches to the flash kernel when the (static) sequence
    length is long enough for it to beat XLA's fused softmax-attention, and to
    the plain path otherwise — the per-config choice is made once at trace
    time, so the compiled step contains exactly one implementation.
    """

    def attention_fn(q, k, v, valid_len=None):
        if causal and valid_len is not None:
            # Match flash_attention's guard on the short-T branch too — a
            # silently dropped valid_len would attend over pad keys.
            raise ValueError("valid_len composes with non-causal attention only")
        if q.shape[1] < min_seq_len:
            from distributed_training_pytorch_tpu.models.vit import dot_product_attention

            if causal:
                return _causal_plain(q, k, v)
            return dot_product_attention(q, k, v, dtype=q.dtype, valid_len=valid_len)
        return flash_attention(q, k, v, causal=causal, valid_len=valid_len, **kwargs)

    return attention_fn


def _causal_plain(q, k, v):
    t = q.shape[1]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    idx = jnp.arange(t)
    logits = jnp.where((idx[:, None] >= idx[None, :])[None, None], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


# ---------------------------------------------------------------------------
# Fused 1x1-conv + BN-apply + ReLU.
#
# A 1x1 conv IS a GEMM: NHWC input flattened to [N, Cin] against [Cin, Cout],
# with the BatchNorm apply folded to a per-output-channel affine
# (a = gamma * rsqrt(var + eps), b = beta - mean * a) and the ReLU as the
# epilogue — one HBM read of x, one write of the activated output, nothing
# materialized in between. ResNet stage-1's 56x56x(64<->256) branches run
# ~28 FLOP/byte on a 240 FLOP/byte v5e — pure bandwidth — so the question is
# only whether a hand-tiled GEMM+epilogue moves more bytes/s than XLA's
# conv+fusion at these shapes (scripts/resnet_pallas_probe.py measures both;
# not measured on today's chip).


def _resolve_act(relu: bool, act: Optional[str]) -> Optional[str]:
    """Normalize the epilogue knobs: ``act`` (None/"relu"/"gelu") wins when
    given; otherwise the legacy ``relu`` bool maps to "relu"/identity."""
    if act is None:
        return "relu" if relu else None
    if act not in ("relu", "gelu"):
        raise ValueError(f"act must be None, 'relu', or 'gelu' (got {act!r})")
    return act


# What the kernel's block buffers may take of the compiler's 16.00M default
# scoped-VMEM limit. Measured on the v5e (libtpu 0.0.34) with Cout whole,
# ConvNeXt-L's last expand (1536 -> 6144) is refused: "Ran out of memory in
# memory space vmem ... Scoped allocation with size 21.87M"; tiled to 1536
# columns it still asks for 18.87M — exactly the x tile plus the
# double-buffered weight and output tiles — "and limit 16.00M". So the plan
# below counts every block buffer, double-buffered, and keeps the sum under
# 12 MiB, leaving the rest of the limit to Mosaic's own temporaries.
_CONV1X1_VMEM_BUDGET = 12 * 2**20


def _conv1x1_block_cols(block_rows, cin, cout, x_bytes, w_bytes, out_bytes) -> int:
    """The Cout tile: all of Cout where the double-buffered x, weight and
    output blocks fit the budget (every ResNet stage-1 shape, ConvNeXt-L up
    to 384 -> 1536), else the largest multiple of the 128-lane tile dividing
    Cout that does (1024 for 768 -> 3072, 512 for 1536 -> 6144). A Cout with
    no such divisor stays whole and the compiler decides."""

    def buffers(bn):
        return 2 * (block_rows * cin * x_bytes + cin * bn * w_bytes + block_rows * bn * out_bytes)

    if buffers(cout) <= _CONV1X1_VMEM_BUDGET:
        return cout
    fitting = [
        bn for bn in range(128, cout, 128)
        if cout % bn == 0 and buffers(bn) <= _CONV1X1_VMEM_BUDGET
    ]
    return max(fitting, default=cout)


def _conv1x1_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, *, act):
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y = acc * a_ref[:] + b_ref[:]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act == "gelu":
        # tanh approximation — matches flax ``nn.gelu`` (approximate=True),
        # the ConvNeXt expand-Dense epilogue this fusion serves. Computed on
        # the f32 pre-activation, so the plain-path parity gap is only the
        # compute-dtype difference (documented tolerance in tests).
        y = jax.nn.gelu(y, approximate=True)
    o_ref[:] = y.astype(o_ref.dtype)


def conv1x1_bn_act(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    *,
    relu: bool = True,
    act: Optional[str] = None,
    block_rows: int = 1024,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``act((x @ w) * scale + bias)`` fused in one Pallas pass.

    ``x``: ``[..., Cin]`` (e.g. NHWC — leading dims flatten to rows);
    ``w``: ``[Cin, Cout]`` (a 1x1 conv kernel squeezed); ``scale``/``bias``:
    ``[Cout]`` — the folded BN apply (identity: ones/zeros). The epilogue
    activation is ``act`` (``"relu"``/``"gelu"``/``None``); when ``act`` is
    unset the legacy ``relu`` bool picks relu vs identity. Grid over row
    blocks x Cout tiles; Cin stays whole, and so does Cout wherever the
    block buffers fit VMEM (every ResNet shape; ConvNeXt-L's two widest
    expands are tiled — :func:`_conv1x1_block_cols`). Matmul on
    the MXU in f32 accumulation; epilogue on the VPU; output cast to
    ``out_dtype`` (default: x.dtype). ``interpret=None`` auto-selects
    (:func:`resolve_interpret`)."""
    act = _resolve_act(relu, act)
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    cin = x.shape[-1]
    if w.shape[0] != cin:
        raise ValueError(f"w {w.shape} does not match x Cin {cin}")
    cout = w.shape[1]
    n = 1
    for d in lead:
        n *= d
    out_dtype = out_dtype or x.dtype
    x2 = x.reshape(n, cin)
    n_pad = -(-n // block_rows) * block_rows
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
    a2 = scale.reshape(1, cout).astype(jnp.float32)
    b2 = bias.reshape(1, cout).astype(jnp.float32)
    bn = _conv1x1_block_cols(
        block_rows, cin, cout,
        x2.dtype.itemsize, w.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
    )
    # Cout tiles innermost: the x tile's block index does not change across
    # them, so it is fetched once per row block.
    call = pl.pallas_call(
        functools.partial(_conv1x1_kernel, act=act),
        grid=(n_pad // block_rows, cout // bn),
        in_specs=[
            pl.BlockSpec((block_rows, cin), lambda i, j: (i, 0)),
            pl.BlockSpec((cin, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, cout), out_dtype),
        interpret=interpret,
        name="conv1x1_bn_act",
    )
    with jax.named_scope("conv1x1_bn_act"):
        out = call(x2, w, a2, b2)
    return out[:n].reshape(*lead, cout)


def _conv1x1_fwd(x, w, scale, bias, act, block_rows, out_dtype, interpret, affine_grads):
    y = conv1x1_bn_act(
        x, w, scale, bias, act=act, relu=False, block_rows=block_rows,
        out_dtype=out_dtype, interpret=interpret,
    )
    return y, (x, w, scale, bias, y)


def _conv1x1_bwd(act, block_rows, out_dtype, interpret, affine_grads, res, g):
    """Standard GEMM backward in XLA dots (same shapes, MXU-friendly).

    relu: dz = g * 1{y>0} * scale — the live mask comes free from the saved
    output, no pre-activation needed. gelu: gelu' needs the pre-activation
    ``u = z*scale + bias`` — z is RECOMPUTED as x @ w (inverting the epilogue
    from y divides by scale, which breaks on the zero-init-gamma BN folds
    this kernel exists to serve) and the exact derivative comes from
    ``jax.vjp`` of the same tanh-approximate gelu the forward ran. Then
    dx = dz @ w^T; dw = x^T @ dz; dscale/dbias reduce the epilogue grads."""
    x, w, scale, bias, y = res
    lead = x.shape[:-1]
    cin, cout = w.shape
    g2 = g.reshape(-1, cout).astype(jnp.float32)
    x2 = x.reshape(-1, cin)
    z = None
    if act == "gelu":
        z = jnp.dot(x2, w, preferred_element_type=jnp.float32)
        u = z * scale.astype(jnp.float32) + bias.astype(jnp.float32)
        _, act_vjp = jax.vjp(lambda t: jax.nn.gelu(t, approximate=True), u)
        (gz,) = act_vjp(g2)  # grad wrt the pre-activation u
    elif act == "relu":
        y2 = y.reshape(-1, cout).astype(jnp.float32)
        gz = jnp.where(y2 > 0, g2, 0.0)
    else:
        gz = g2
    if affine_grads:
        dbias = jnp.sum(gz, axis=0)
        if z is None:
            z = jnp.dot(x2, w, preferred_element_type=jnp.float32)
        dscale = jnp.sum(gz * z, axis=0)
    else:
        # Epilogue declared non-trainable (identity constants): skip the z
        # recompute GEMM entirely (relu/identity only — gelu already paid it).
        dbias = jnp.zeros_like(bias)
        dscale = jnp.zeros_like(scale)
    dz = gz * scale  # [N, cout] f32
    dx = (dz.astype(x.dtype) @ w.T.astype(x.dtype)).reshape(*lead, cin)
    dw = jnp.dot(
        x2.T, dz.astype(x.dtype), preferred_element_type=jnp.float32
    ).astype(w.dtype)
    return dx.astype(x.dtype), dw, dscale.astype(scale.dtype), dbias.astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _conv1x1_diff(x, w, scale, bias, act, block_rows, out_dtype, interpret, affine_grads):
    return conv1x1_bn_act(
        x, w, scale, bias, act=act, relu=False, block_rows=block_rows,
        out_dtype=out_dtype, interpret=interpret,
    )


_conv1x1_diff.defvjp(_conv1x1_fwd, _conv1x1_bwd)


def conv1x1_bn_act_diff(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    *,
    relu: bool = True,
    act: Optional[str] = None,
    block_rows: int = 1024,
    out_dtype=None,
    interpret: Optional[bool] = None,
    affine_grads: bool = True,
) -> jax.Array:
    """Differentiable :func:`conv1x1_bn_act`: Pallas forward, standard-GEMM
    XLA backward (custom VJP above). The primal output is the only residual
    beyond the inputs — nothing autodiff would not already keep.

    ``affine_grads=False`` declares scale/bias non-trainable constants (the
    ``PallasConv1x1`` identity-epilogue use) and returns zero gradients for
    them, skipping the backward's z-recompute GEMM (relu/identity epilogues;
    gelu recomputes z for its derivative regardless)."""
    return _conv1x1_diff(
        x, w, scale, bias, _resolve_act(relu, act), block_rows,
        out_dtype or x.dtype, resolve_interpret(interpret), affine_grads,
    )
