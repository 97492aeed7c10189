"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Entirely absent from the reference (no attention, no sequence axis —
SURVEY.md §5 'long-context'); built TPU-first per the driver's long-context
mandate. Both strategies run inside ``shard_map`` over the mesh's ``seq``
axis, so a sequence ``s``-times longer than one device's HBM allows fits:

* :func:`ring_attention` — blockwise attention with online softmax; K/V
  blocks rotate around the ring via ``lax.ppermute`` while each device keeps
  its Q shard. Compute on block ``i`` overlaps the transfer of block ``i+1``
  (XLA's latency-hiding scheduler pipelines the permute) — the
  Liu & Abbeel ring-attention schedule, implemented as a ``lax.scan`` of MXU
  matmuls rather than a hand-scheduled kernel.
* :func:`ulysses_attention` — DeepSpeed-Ulysses: ``lax.all_to_all`` swaps the
  sequence shard for a head shard, runs *dense* local attention per head
  group, and swaps back. Cheaper collectives for moderate sequence lengths;
  requires ``num_heads % seq_devices == 0``.

Both take ``[B, T, H, D]`` global arrays (T sharded over ``seq``) and return
the same layout; numerics match dense attention to float tolerance (tested on
the 8-device CPU mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_pytorch_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, bias=None):
    """One Q-block x K-block attention: returns (unnormalized out, row max,
    row sumexp) for online-softmax accumulation. Shapes [B, Tq, H, D] x
    [B, Tk, H, D]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    m = logits.max(axis=-1)  # [B, H, Tq]
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)  # [B, H, Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    return o, m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis``. [B, T, H, D].

    ``impl``:

    * ``"dense"`` — each ring step materializes the [B, H, Tq, Tk] block
      logits (fine for moderate per-shard T; O(T_local^2) memory).
    * ``"flash"`` — each ring step runs the Pallas flash kernel on the
      visiting K/V block and merges via the kernel's LSE statistics, so
      per-shard memory stays O(T_local) and the [Tq, Tk] scores never exist.
      Under a causal mask, fully-masked blocks (owner > self) skip the kernel
      outright — about half the ring FLOPs, which the dense path spends on
      fully-bias-masked matmuls. Backward is the blockwise flash
      decomposition run as a reverse ring (dk/dv accumulate on the rotating
      blocks; one ring-level custom VJP owns the schedule).
    * ``"auto"`` — flash on TPU when the per-shard sequence clears the
      kernel's measured crossover (``ops.pallas.FLASH_MIN_SEQ_LEN``), else
      dense.
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r}")
    if impl not in ("auto", "dense", "flash"):
        raise ValueError(f"impl must be auto|dense|flash, got {impl!r}")
    if impl == "auto":
        from distributed_training_pytorch_tpu.ops.pallas import FLASH_MIN_SEQ_LEN

        t_local = q.shape[1] // mesh.shape[axis]
        impl = (
            "flash"
            if jax.default_backend() == "tpu" and t_local >= FLASH_MIN_SEQ_LEN
            else "dense"
        )
    if impl == "flash":
        return _ring_attention_flash(q, k, v, mesh, axis=axis, causal=causal)
    scale = q.shape[-1] ** -0.5

    def kernel(q, k, v):
        s = lax.psum(1, axis)  # ring size
        my = lax.axis_index(axis)
        t_local = q.shape[1]
        q_pos = my * t_local + jnp.arange(t_local)  # global Q positions
        perm = [(i, (i + 1) % s) for i in range(s)]

        def block_bias(step):
            if not causal:
                return None
            # Who produced this K/V block: it has moved `step` hops forward.
            owner = (my - step) % s
            k_pos = owner * t_local + jnp.arange(t_local)
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, _NEG_INF)
            return bias[None, None]  # [1, 1, Tq, Tk]

        def merge(acc, step, k_blk, v_blk):
            o, m, l = acc
            o_b, m_b, l_b = _block_attn(q, k_blk, v_blk, scale, block_bias(step))
            m_new = jnp.maximum(m, m_b)  # online softmax merge
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(m_b - m_new)
            o = o * alpha.transpose(0, 2, 1)[..., None] + o_b * beta.transpose(0, 2, 1)[..., None]
            l = l * alpha + l_b * beta
            return o, m_new, l

        def body(carry, step):
            # Rotate first, compute after: the own (step-0) block is handled
            # outside the scan, so no rotation result is ever discarded.
            o, m, l, k_blk, v_blk = carry
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            o, m, l = merge((o, m, l), step, k_blk, v_blk)
            return (o, m, l, k_blk, v_blk), None

        B, T, H, D = q.shape
        o0 = jnp.zeros((B, T, H, D), jnp.float32)
        m0 = jnp.full((B, H, T), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, T), jnp.float32)
        acc = merge((o0, m0, l0), 0, k, v)  # own block, no communication
        (o, m, l, _, _), _ = lax.scan(body, acc + (k, v), jnp.arange(1, s))
        o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return o.astype(q.dtype)

    spec = P(None, axis, None, None)
    return shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def _ring_attention_flash(q, k, v, mesh, *, axis, causal):
    """Ring attention with the Pallas flash kernel as the per-block compute.

    Forward: each device keeps its q shard; K/V blocks rotate; every step
    runs ``flash_block_fwd`` (block-normalized output + LSE) and merges into
    the running output with ``logaddexp`` weights — the online softmax across
    blocks, with the within-block online softmax living in the kernel.

    Backward: the standard blockwise flash decomposition, run as a second
    ring. ``delta = rowsum(dO * O)`` and the final LSE are global per-q-row
    statistics, so each visiting K/V block's (dq, dk, dv) contributions are
    computable locally by the flash backward kernel; dq accumulates in place
    while dk/dv accumulate on buffers that rotate *with* their K/V blocks and
    arrive home after a full loop. A custom VJP around the two shard_maps
    owns the schedule (autodiff never sees the kernel internals).
    """
    s = mesh.shape[axis]
    from distributed_training_pytorch_tpu.ops.pallas import (
        flash_block_bwd,
        flash_block_fwd,
    )

    perm = [(i, (i + 1) % s) for i in range(s)]

    def block_type(step):
        # Causal block classification: the visiting block left its owner
        # `step` hops back. 0 = fully masked (skip), 1 = diagonal (local
        # causal), 2 = fully visible.
        my = lax.axis_index(axis)
        owner = (my - step) % s
        return jnp.where(owner == my, 1, jnp.where(owner < my, 2, 0))

    def fwd_kernel(q, k, v):
        b, tl, h, d = q.shape

        def fwd_block(step, k_blk, v_blk):
            if not causal:
                return flash_block_fwd(q, k_blk, v_blk, causal=False)

            def skip(_k, _v):
                return (
                    jnp.zeros((b, tl, h, d), q.dtype),
                    jnp.full((b, h, tl), _NEG_INF, jnp.float32),
                )

            return lax.switch(
                block_type(step),
                [
                    skip,
                    lambda kb, vb: flash_block_fwd(q, kb, vb, causal=True),
                    lambda kb, vb: flash_block_fwd(q, kb, vb, causal=False),
                ],
                k_blk,
                v_blk,
            )

        def merge(acc, step, k_blk, v_blk):
            o, lse = acc
            o_b, lse_b = fwd_block(step, k_blk, v_blk)
            lse_new = jnp.logaddexp(lse, lse_b)
            w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
            w_new = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
            return o * w_old + o_b.astype(jnp.float32) * w_new, lse_new

        o0 = jnp.zeros((b, tl, h, d), jnp.float32)
        lse0 = jnp.full((b, h, tl), _NEG_INF, jnp.float32)
        acc = merge((o0, lse0), 0, k, v)  # own block, no communication

        def body(carry, step):
            o, lse, k_blk, v_blk = carry
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            o, lse = merge((o, lse), step, k_blk, v_blk)
            return (o, lse, k_blk, v_blk), None

        (o, lse, _, _), _ = lax.scan(body, acc + (k, v), jnp.arange(1, s))
        return o.astype(q.dtype), lse

    def bwd_kernel(q, k, v, g, o, lse):
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1)  # [B, H, TL]

        def bwd_block(step, k_blk, v_blk):
            if not causal:
                return flash_block_bwd(
                    q, k_blk, v_blk, g, lse, delta, causal=False
                )

            def skip(_k, _v):
                return (
                    jnp.zeros_like(q),
                    jnp.zeros_like(k_blk),
                    jnp.zeros_like(v_blk),
                )

            return lax.switch(
                block_type(step),
                [
                    skip,
                    lambda kb, vb: flash_block_bwd(
                        q, kb, vb, g, lse, delta, causal=True
                    ),
                    lambda kb, vb: flash_block_bwd(
                        q, kb, vb, g, lse, delta, causal=False
                    ),
                ],
                k_blk,
                v_blk,
            )

        # Accumulate dq/dk/dv in float32 across ring steps (the kernels
        # already accumulate f32 *within* a block; without this the
        # cross-step += happens in the input dtype and rounding error grows
        # with ring size — matching the f32 statistics the forward keeps).
        # dk/dv therefore ride the ring as f32: 2x the ICI bytes of the
        # bf16 activations, bought for s-step-independent gradient error.
        f32 = lambda t: t.astype(jnp.float32)
        dq0, dk0, dv0 = map(f32, bwd_block(0, k, v))

        def body(carry, step):
            dq, k_blk, v_blk, dk_blk, dv_blk = carry
            # dk/dv ride the same rotation as their K/V blocks so each device
            # adds its contribution to the visiting block in place.
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            dk_blk = lax.ppermute(dk_blk, axis, perm)
            dv_blk = lax.ppermute(dv_blk, axis, perm)
            dq_c, dk_c, dv_c = bwd_block(step, k_blk, v_blk)
            return (dq + f32(dq_c), k_blk, v_blk, dk_blk + f32(dk_c), dv_blk + f32(dv_c)), None

        (dq, _, _, dk, dv), _ = lax.scan(
            body, (dq0, k, v, dk0, dv0), jnp.arange(1, s)
        )
        # s-1 hops so far; one more brings each dk/dv block home.
        dk = lax.ppermute(dk, axis, perm)
        dv = lax.ppermute(dv, axis, perm)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    spec = P(None, axis, None, None)
    lse_spec = P(None, None, axis)
    fwd_sm = shard_map(
        fwd_kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, lse_spec),
        check_vma=False,
    )
    bwd_sm = shard_map(
        bwd_kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec, lse_spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )

    @jax.custom_vjp
    def ring(q, k, v):
        return fwd_sm(q, k, v)[0]

    def ring_fwd(q, k, v):
        o, lse = fwd_sm(q, k, v)
        return o, (q, k, v, o, lse)

    def ring_bwd(res, g):
        q, k, v, o, lse = res
        return bwd_sm(q, k, v, g, o, lse)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    use_flash: bool | None = None,
) -> jax.Array:
    """DeepSpeed-Ulysses sequence parallelism: all-to-all to head-sharded
    layout, dense local attention, all-to-all back. [B, T, H, D], T sharded
    on ``axis``; requires H divisible by the axis size.

    ``use_flash``: run the local attention through the Pallas flash kernel —
    after the all-to-all each device holds the FULL sequence for its head
    group, exactly the long-T shape where the kernel beats XLA (and where the
    O(T^2) score tensor may not even fit). None = auto: flash on TPU when the
    global sequence is long enough (``ops.pallas.FLASH_MIN_SEQ_LEN``).
    Differentiable either way (the kernel carries its own flash backward).
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r}")
    s = mesh.shape[axis]
    if q.shape[2] % s:
        raise ValueError(f"num_heads {q.shape[2]} not divisible by seq devices {s}")
    scale = q.shape[-1] ** -0.5

    def kernel(q, k, v):
        # [B, T/s, H, D] -> [B, T, H/s, D]: scatter heads, gather sequence.
        def seq_to_heads(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

        def heads_to_seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        T = qh.shape[1]
        from distributed_training_pytorch_tpu.ops.pallas import (
            FLASH_MIN_SEQ_LEN,
            flash_attention,
        )

        flash = use_flash
        if flash is None:
            flash = jax.default_backend() == "tpu" and T >= FLASH_MIN_SEQ_LEN
        if flash:
            o = flash_attention(qh, kh, vh, causal=causal)
        else:
            bias = None
            if causal:
                pos = jnp.arange(T)
                bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, _NEG_INF)[None, None]
            o, m, l = _block_attn(qh, kh, vh, scale, bias)
            o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return heads_to_seq(o.astype(q.dtype))

    spec = P(None, axis, None, None)
    return shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
