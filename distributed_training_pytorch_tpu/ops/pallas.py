"""Pallas TPU kernels: fused flash attention.

The reference gets its fused kernels from cuDNN via torch
(``/root/reference/requirements.txt:12-24``, ``model/vgg16.py:9-14``); the
TPU-native equivalent obligation (SURVEY.md §2b) is custom Pallas kernels
where plain XLA underperforms — attention being the canonical case: a
materialized ``[B, H, T, T]`` score tensor is HBM-bandwidth-bound, while the
flash formulation streams K/V blocks through VMEM with an online softmax and
never materializes the scores.

Public surface:

* :func:`flash_attention` — ``[B, T, H, D]`` q/k/v -> ``[B, T, H, D]``, same
  contract as ``models.vit.dot_product_attention`` (scale = D**-0.5, optional
  causal mask), differentiable (custom VJP, flash backward kernels).
* :func:`make_attention_fn` — adapter for ``models.vit.MultiHeadAttention``'s
  ``attention_fn`` hook; picks the kernel on TPU and the plain XLA path
  elsewhere.

Kernel design (see /opt/skills/guides/pallas_guide.md): grid over
``(batch, head, q-block)``; K/V live in VMEM as whole ``[T, D]`` slabs per
(batch, head) — fine through ~32k tokens at D=64/128; beyond that, sequence
parallelism (``parallel.ring_attention``) shards T across chips and each shard
re-enters this kernel. Softmax statistics are carried in float32; matmuls run
on the MXU with ``preferred_element_type=float32``. The backward pass is the
standard flash decomposition: a delta precompute (``rowsum(dO * O)``), a
dq kernel gridded over q-blocks, and a dk/dv kernel gridded over k-blocks —
so the [T, T] score matrix is never materialized in either direction.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30  # large-negative logit for masked positions (f32-safe)


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


# 1024-blocks: bigger q-tiles amortize the K/V streaming loop and fill the
# MXU (block sizes not swept on today's chip). Blocks auto-clamp to T
# (rounded up to the 128-lane tile, _block_size), so short sequences are
# unaffected.
_DEFAULT_BLOCK_Q = 1024
_DEFAULT_BLOCK_K = 1024


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


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k, seq_len, causal):
    """One q-block against all k-blocks, online softmax. Refs are
    (1, 1, bq, D) / (1, 1, Tp, D) blocks; statistics in f32."""
    bq = q_ref.shape[2]
    d = q_ref.shape[3]
    t_pad = k_ref.shape[2]
    n_k = t_pad // block_k

    # Matmuls run in the input dtype (bf16 in production — one MXU pass; an
    # f32 cast would force the 3x-slower f32 path) with f32 accumulation;
    # softmax statistics and the scale multiply stay f32.
    q = q_ref[0, 0]  # [bq, D]
    q_idx = pl.program_id(2) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]  # [bk, D]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]  # [bk, D]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk] f32
        k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = k_idx < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_idx >= k_idx)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m - m_new)  # [bq, 1]
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    # Padded q rows (and fully-masked causal rows cannot occur: row i always
    # sees k=i) have l=0 only when the whole row was padding; guard the divide.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    # Stats live as [1, bq] lane-major rows: a [B, H, 1, T] buffer pads only
    # its singleton sublane dim (8x on 1), where a [..., T, 1] layout would
    # pad the lane dim 128x (measured 384MB/layer on ViT-B — OOM).
    lse_ref[0, 0] = jnp.transpose(m + jnp.log(l_safe), (1, 0))  # [1, bq]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, block_k, seq_len, causal
):
    """dq for one q-block: dq_i = scale * sum_j (p_ij * (dp_ij - delta_i)) k_j."""
    bq = q_ref.shape[2]
    d = q_ref.shape[3]
    t_pad = k_ref.shape[2]
    n_k = t_pad // block_k

    q = q_ref[0, 0]
    do = do_ref[0, 0]  # [bq, D]
    lse = jnp.transpose(lse_ref[0, 0], (1, 0))  # [1, bq] -> [bq, 1]
    delta = jnp.transpose(delta_ref[0, 0], (1, 0))
    q_idx = pl.program_id(2) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = k_idx < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_idx >= k_idx)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_k, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, block_q, seq_len, causal
):
    """dk/dv for one k-block, looping over q-blocks:
    dv_j = sum_i p_ij^T do_i ; dk_j = scale * sum_i (p_ij * (dp_ij - delta_i))^T q_i."""
    bk = k_ref.shape[2]
    d = k_ref.shape[3]
    t_pad = q_ref.shape[2]
    n_q = t_pad // block_q

    k = k_ref[0, 0]  # [bk, D]
    v = v_ref[0, 0]
    k_idx = pl.program_id(2) * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = jnp.transpose(lse_ref[0, 0, :, pl.ds(i * block_q, block_q)], (1, 0))
        delta = jnp.transpose(delta_ref[0, 0, :, pl.ds(i * block_q, block_q)], (1, 0))
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk] f32
        q_idx = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        mask = k_idx < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_idx >= k_idx)
        s = jnp.where(mask, s, NEG_INF)
        # [bq, bk]. Padded q rows (zero q, zero-padded lse) give s=0, lse=0,
        # p=1 — NOT p=0. Their dv/dk contributions still vanish only because
        # dO and delta are zero-padded (dv += p^T·dO = 0; ds = p*(dp-delta)
        # has dp = dO·v^T = 0 and delta = 0). Keep the dO/delta zero-padding.
        p = jnp.exp(s - lse)
        dv_new = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, n_q, body, (dk0, dv0))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Wrapper with custom VJP
# ---------------------------------------------------------------------------


def _to_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))  # [B,T,H,D] -> [B,H,T,D]


def _from_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _fwd_call(qt, kt, vt, t_k, causal, bq, bk, interpret):
    """Forward pallas call on padded [B, H, T*, D] operands -> (o, lse) in the
    padded layout. Shared by flash_attention (square T) and the ring block
    path (Tq from the resident shard, Tk from the visiting block).

    Each kernel of this file is called under a stable ``name=`` inside a
    ``jax.named_scope`` of the same name (``flash_fwd``, ``flash_dq``,
    ``flash_dkv``, ``conv1x1_bn_act``): the kernel's Python name reaches no
    device trace on today's runtime, and a reader of one matches these."""
    b, h, tq_pad, d = qt.shape
    tk_pad = kt.shape[2]
    kernel = functools.partial(
        _fwd_kernel, scale=d**-0.5, block_k=bk, seq_len=t_k, causal=causal
    )
    call = pl.pallas_call(
        kernel,
        grid=(b, h, tq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, tk_pad, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, tk_pad, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq_pad, d), qt.dtype),
            jax.ShapeDtypeStruct((b, h, 1, tq_pad), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        return call(qt, kt, vt)


def _fwd_impl(q, k, v, causal, block_q, block_k, interpret, valid_len=None):
    b, t, h, d = q.shape
    t_k = t if valid_len is None else valid_len  # kernels mask keys >= t_k
    qt, kt, vt, bq, bk = _ring_pad(q, k, v, block_q, block_k)
    o, lse = _fwd_call(qt, kt, vt, t_k, causal, bq, bk, interpret)
    return o[:, :, :t, :], lse[:, :, :, :t], (qt, kt, vt)


def _dq_call(qt, kt, vt, do, lse_p, delta, t_q, t_k, causal, bq, bk, interpret):
    """dq pallas call on padded [B, H, T*, D] operands. ``t_k`` masks padded
    K rows; ``t_q`` is unused by the kernel (padded q rows produce garbage dq
    rows that callers slice off) but kept for call-site clarity."""
    b, h, tq_pad, d = qt.shape
    tk_pad = kt.shape[2]
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=d**-0.5, block_k=bk, seq_len=t_k, causal=causal
    )
    call = pl.pallas_call(
        dq_kernel,
        grid=(b, h, tq_pad // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, tk_pad, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, tk_pad, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, 0, qi)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq_pad, d), qt.dtype),
        interpret=interpret,
        name="flash_dq",
    )
    with jax.named_scope("flash_dq"):
        return call(qt, kt, vt, do, lse_p, delta)


def _dkv_call(qt, kt, vt, do, lse_p, delta, t_q, t_k, causal, bq, bk, interpret):
    """dk/dv pallas call on padded [B, H, T*, D] operands. Padded q rows are
    harmless because ``do``/``delta`` are zero-padded (see _bwd_dkv_kernel);
    ``t_k`` masks padded K rows."""
    b, h, tq_pad, d = qt.shape
    tk_pad = kt.shape[2]
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=d**-0.5, block_q=bq, seq_len=t_k, causal=causal
    )
    call = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, tk_pad // bk),
        in_specs=[
            pl.BlockSpec((1, 1, tq_pad, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, tq_pad, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, tq_pad), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, tq_pad), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk_pad, d), kt.dtype),
            jax.ShapeDtypeStruct((b, h, tk_pad, d), vt.dtype),
        ],
        interpret=interpret,
        name="flash_dkv",
    )
    with jax.named_scope("flash_dkv"):
        return call(qt, kt, vt, do, lse_p, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, valid_len):
    o, _, _ = _fwd_impl(q, k, v, causal, block_q, block_k, interpret, valid_len)
    return _from_bhtd(o)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, valid_len):
    o, lse, (qt, kt, vt) = _fwd_impl(
        q, k, v, causal, block_q, block_k, interpret, valid_len
    )
    return _from_bhtd(o), (qt, kt, vt, o, lse, q.shape)


def _flash_bwd(causal, block_q, block_k, interpret, valid_len, res, g):
    qt, kt, vt, o, lse, q_shape = res
    b, t, h, d = q_shape
    t_k = t if valid_len is None else valid_len
    bq = _block_size(block_q, t)
    bk = _block_size(block_k, t)
    tq_pad = qt.shape[2]

    do = _pad_to(_to_bhtd(g), tq_pad, 2)
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise precompute, plain XLA.
    delta = jnp.sum(
        do.astype(jnp.float32) * _pad_to(o, tq_pad, 2).astype(jnp.float32),
        axis=-1,
    )[:, :, None, :]  # [B, H, 1, Tq_pad]
    lse_p = _pad_to(lse, tq_pad, 3)

    dq = _dq_call(qt, kt, vt, do, lse_p, delta, t, t_k, causal, bq, bk, interpret)
    dk, dv = _dkv_call(qt, kt, vt, do, lse_p, delta, t, t_k, causal, bq, bk, interpret)

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
# backward wrappers compute one block's dq / dk/dv contributions given the
# *global* lse/delta of the resident q shard — exactly the flash
# decomposition, applied blockwise across devices. All take/return
# ``[B, T, H, D]`` (lse/delta ``[B, H, T]``).


def _ring_pad(q, k, v, block_q, block_k):
    tq, tk = q.shape[1], k.shape[1]
    bq = _block_size(block_q, tq)
    bk = _block_size(block_k, tk)
    qt = _pad_to(_to_bhtd(q), pl.cdiv(tq, bq) * bq, 2)
    kt = _pad_to(_to_bhtd(k), pl.cdiv(tk, bk) * bk, 2)
    vt = _pad_to(_to_bhtd(v), pl.cdiv(tk, bk) * bk, 2)
    return qt, kt, vt, bq, bk


def flash_block_fwd(
    q, k, v, *, causal=False,
    block_q=_DEFAULT_BLOCK_Q, block_k=_DEFAULT_BLOCK_K, interpret=None,
):
    """One (q-shard x k/v-block) flash pass -> ``(o, lse)``; o is
    block-normalized, lse = log-sum-exp of this block's logits per q row
    (what the cross-block online merge needs). Not differentiable — the ring
    owns the VJP."""
    interpret = resolve_interpret(interpret)
    tq, tk = q.shape[1], k.shape[1]
    qt, kt, vt, bq, bk = _ring_pad(q, k, v, block_q, block_k)
    o, lse = _fwd_call(qt, kt, vt, tk, causal, bq, bk, interpret)
    return _from_bhtd(o[:, :, :tq, :]), lse[:, :, 0, :tq]


def flash_block_bwd(
    q, k, v, do, lse, delta, *, causal=False,
    block_q=_DEFAULT_BLOCK_Q, block_k=_DEFAULT_BLOCK_K, interpret=None,
):
    """One block's backward contributions ``(dq, dk, dv)`` given the global
    ``lse``/``delta`` ``[B, H, Tq]`` of the resident q shard."""
    interpret = resolve_interpret(interpret)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    qt, kt, vt, bq, bk = _ring_pad(q, k, v, block_q, block_k)
    tq_pad = qt.shape[2]
    dot = _pad_to(_to_bhtd(do), tq_pad, 2)
    lse_p = _pad_to(lse[:, :, None, :], tq_pad, 3)
    delta_p = _pad_to(delta[:, :, None, :], tq_pad, 3)
    dq = _dq_call(qt, kt, vt, dot, lse_p, delta_p, tq, tk, causal, bq, bk, interpret)
    dk, dv = _dkv_call(qt, kt, vt, dot, lse_p, delta_p, tq, tk, causal, bq, bk, interpret)
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
    block_q: int = _DEFAULT_BLOCK_Q,
    block_k: int = _DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
    valid_len: Optional[int] = None,
) -> jax.Array:
    """Fused flash attention on ``[B, T, H, D]`` tensors.

    Numerics match ``models.vit.dot_product_attention`` (softmax statistics in
    float32, scale ``D**-0.5``); memory is O(T) per (batch, head) instead of
    the O(T^2) score tensor. ``interpret=None`` auto-selects
    (:func:`resolve_interpret`). ``valid_len`` masks key
    positions >= it — for caller-padded sequences (``ViT.pad_seq_to``); the
    kernels' own seq_len masking does the work, no score tensor or bias mask
    is ever built.
    """
    if q.ndim != 4 or q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"expected matching [B,T,H,D] q/k/v, got {q.shape}/{k.shape}/{v.shape}")
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
    """How a ``[B, T, H, D]`` attention operand is split over the ambient
    mesh (the one ``TrainEngine``/``InferEngine`` set around their jits), or
    None when the kernel should be called as is.

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
    from distributed_training_pytorch_tpu.parallel.mesh import (
        DATA_AXIS,
        FSDP_AXIS,
        TENSOR_AXIS,
    )

    b, _, h, _ = shape
    sizes = mesh.shape
    batch_axes = tuple(a for a in (DATA_AXIS, FSDP_AXIS) if sizes.get(a, 1) > 1)
    if b % math.prod(sizes[a] for a in batch_axes):
        batch_axes = ()
    heads = sizes.get(TENSOR_AXIS, 1)
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
