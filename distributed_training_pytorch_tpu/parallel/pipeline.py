"""Pipeline parallelism — single-program collective-permute schedules.

Not present in the reference (its only strategy is DDP data parallelism,
``trainer/trainer.py:52``); built TPU-first to complete the parallelism matrix
(dp / fsdp / tp / sp / pp / ep). The design is the single-program collective-
permute pipeline (the TPU-idiomatic formulation — no per-stage processes, no
send/recv threads as in GPU PP runtimes):

* the mesh gets a ``pipe`` axis of size ``S``; the trunk is a stack of
  ``S * n_virtual`` homogeneous *virtual* stages, virtual stage ``k`` living
  on device ``k % S`` (``n_virtual`` chunks per device — the Megatron-style
  interleaved placement). Stage parameters are one stacked
  ``[S * n_virtual, ...]`` pytree sharded so each device holds its chunks;
* one jitted program runs ``n_micro * n_virtual + S - 1`` ticks of a
  ``lax.scan``; each tick every device applies one virtual stage to its
  current activation and passes the result to its ring successor with a
  single ``lax.ppermute`` — XLA overlaps the permute with the next tick's
  compute. Chunk transitions (…device S-1 chunk c -> device 0 chunk c+1…)
  ride the same ring edge, so interleaving adds no new communication
  patterns;
* the classic pipeline bubble shrinks from GPipe's ``(S-1)/(M+S-1)`` to
  ``((S-1)/v) / (M + (S-1)/v)`` with ``v = n_virtual`` chunks per device
  (each tick now costs ``1/v`` of a device's layer budget) — see
  :func:`bubble_fraction`; a schedule test asserts the v=2 bubble beats
  GPipe at M=8/S=4;
* microbatches are *sharded* over the ``pipe`` axis (device ``d`` holds the
  feed for microbatches ``m % S == d``) and delivered to stage 0 just in
  time through a one-slot rotating ring buffer — per-device feed memory is
  ``M/S`` microbatches and per-tick feed traffic is one microbatch, the same
  order as the activation ring itself. ``feed="replicated"`` keeps the old
  broadcast feed for microbatch counts not divisible by ``S``;
* heterogeneous ends: ``first=(params, fn)`` (e.g. an embedding) runs over
  the feed shards *before* the ring — data-parallel across the pipe group,
  not replicated — and ``last=(params, fn)`` (e.g. the LM head) runs over a
  ``psum_scatter`` of the emitted outputs, again ``1/S`` of the work per
  device. ``embed -> blocks -> head`` therefore pipelines in one call;
* autodiff through the scan + ppermute yields the reverse-schedule backward
  for free; ``remat=True`` wraps each stage application in
  ``jax.checkpoint`` so the backward recomputes stage activations instead of
  stashing every tick's residuals (the memory lever 1F1B buys on GPU
  runtimes, expressed the XLA way).

Composability: the ``pipe`` axis is orthogonal to ``data``/``tensor``/``seq``,
so each stage body may itself be data-parallel or TP-sharded.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P

from distributed_training_pytorch_tpu.parallel.mesh import PIPE_AXIS

__all__ = [
    "PIPE_AXIS",
    "pipeline_apply",
    "stack_stage_params",
    "bubble_fraction",
    "schedule_stats",
]


def stack_stage_params(params_list) -> Any:
    """Stack per-stage parameter pytrees into one ``[n_stages, ...]`` pytree
    (what :func:`pipeline_apply` consumes; shard the leading axis over
    ``pipe``). With ``n_virtual > 1`` pass all ``S * n_virtual`` virtual
    stages in network order — virtual stage ``k`` is chunk ``k // S`` on
    device ``k % S``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def bubble_fraction(n_micro: int, n_stages: int, n_virtual: int = 1) -> float:
    """Idle fraction of the schedule: ``1 - useful_ticks / total_ticks``.

    Every device is busy for exactly ``n_micro * n_virtual`` of the
    ``n_micro * n_virtual + n_stages - 1`` ticks, and with ``v`` chunks per
    device a tick costs ``1/v`` of the per-device layer budget — so in
    stage-time units the bubble is ``((S-1)/v) / (M + (S-1)/v)``, GPipe's
    ``(S-1)/(M+S-1)`` at ``v=1``, strictly smaller for ``v>1``.
    """
    total = n_micro * n_virtual + n_stages - 1
    return 1.0 - (n_micro * n_virtual) / total


def schedule_stats(n_micro: int, n_stages: int, n_virtual: int = 1) -> dict:
    """Count the tick grid (device x tick) of the schedule — the *measured*
    counterpart of :func:`bubble_fraction` (the two must agree; tested).

    Simulates the same activation logic as the compiled program: device ``d``
    is active at tick ``t`` iff ``0 <= t - d < n_micro * n_virtual``.
    """
    M, S, v = n_micro, n_stages, n_virtual
    total_ticks = M * v + S - 1
    active = sum(
        1 for d in range(S) for t in range(total_ticks) if 0 <= t - d < M * v
    )
    total = S * total_ticks
    return {
        "total_ticks": total_ticks,
        "device_ticks": total,
        "active_device_ticks": active,
        "bubble_fraction": 1.0 - active / total,
    }


def _identity_end(params, x):
    return x


def pipeline_apply(
    stage_params: Any,
    microbatches: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis: str = PIPE_AXIS,
    n_virtual: int = 1,
    feed: str = "auto",
    first: tuple[Any, Callable] | None = None,
    last: tuple[Any, Callable] | None = None,
    remat: bool = False,
    extra_manual_axes: tuple[str, ...] = (),
    stage_param_specs: Any | None = None,
) -> jax.Array:
    """Run ``microbatches`` through the pipelined (virtual-)stage stack.

    Args:
      stage_params: pytree whose leaves lead with ``[S * n_virtual, ...]``
        (``S = mesh.shape[axis]``), virtual stage ``k`` = chunk ``k // S`` on
        device ``k % S``.
      microbatches: ``[n_micro, micro_batch, ...]`` inputs for the first
        stage (token ids / images when ``first`` is given, else trunk
        activations).
      stage_fn: ``(stage_params_slice, x) -> y`` with ``y.shape == x.shape``
        (homogeneous trunk — activation shapes can't change across a ring).
      mesh: mesh containing ``axis``. Note ``create_mesh`` builds canonical
        axes only (``mesh.AXIS_ORDER``); a non-canonical ``axis`` name needs
        a hand-built ``jax.sharding.Mesh``.
      n_virtual: chunks per device (Megatron-style interleaving); ``> 1``
        requires ``n_micro % S == 0`` and shrinks the bubble (see
        :func:`bubble_fraction`).
      feed: ``"sharded"`` (microbatch feed sharded over ``axis``; needs
        ``n_micro % S == 0``), ``"replicated"``, or ``"auto"`` (sharded when
        divisible).
      first: optional ``(params, fn)`` applied to each feed microbatch before
        the ring (embedding et al.) — runs sharded over the pipe group under
        ``feed="sharded"``; with a replicated feed every device applies it to
        every microbatch (S-fold redundant, like any replicated compute).
        ``fn(params, mb) -> x0`` may change the trailing shape; all ring
        activations take ``x0``'s shape.
      last: optional ``(params, fn)`` applied to each emitted output after
        the ring, sharded over the pipe group when ``n_micro % S == 0``
        (LM head et al.).
      remat: wrap each stage application in ``jax.checkpoint`` — backward
        recomputes stage activations instead of stashing every tick's
        residuals (activation-memory lever; schedule unchanged).
      extra_manual_axes: additional mesh axes made MANUAL inside the ring
        region (e.g. ``("expert",)``). Nested ``shard_map`` is rejected by
        Shardy ("axis already bound by a parent manual_computation"), so a
        stage body that needs hand-written collectives over another axis —
        the ``moe.manual_expert_ffn_local`` workaround for the
        data x expert x pipe GSPMD CHECK — declares that axis here and uses
        ``jax.lax.psum``/``all_to_all`` over it directly. Activations are
        treated as replicated over these axes; stage params shard per
        ``stage_param_specs``.
      stage_param_specs: pytree matching ONE stage's params whose leaves are
        ``PartitionSpec``s over the non-stage dims (e.g. ``P("expert")`` for
        a ``[E, d, h]`` expert slab, ``P()`` for replicated leaves). Required
        exactly when ``extra_manual_axes`` shards any stage param; the stage
        fn then receives LOCAL slabs.

    Returns ``[n_micro, micro_batch, ...]`` outputs of the last virtual
    stage (after ``last`` if given), replicated over ``axis``.
    Differentiable (reverse pipeline via autodiff).
    """
    S = mesh.shape[axis]
    v = int(n_virtual)
    if v < 1:
        raise ValueError(f"n_virtual must be >= 1, got {v}")
    M = microbatches.shape[0]
    if M < 1:
        raise ValueError("need at least one microbatch")
    VS = S * v
    lead = jax.tree.leaves(stage_params)[0].shape[0]
    if lead != VS:
        raise ValueError(
            f"stage_params lead with {lead} stages but mesh axis {axis!r} "
            f"has {S} devices x {v} virtual chunks = {VS}"
        )
    if v > 1 and M % S:
        raise ValueError(
            f"interleaved schedule (n_virtual={v}) needs n_micro % {S} == 0, "
            f"got n_micro={M} — the chunk round-robin advances in groups of S"
        )
    if feed == "auto":
        feed = "sharded" if M % S == 0 else "replicated"
    if feed not in ("sharded", "replicated"):
        raise ValueError(f"feed must be sharded/replicated/auto, got {feed!r}")
    if feed == "sharded" and M % S:
        raise ValueError(f"sharded feed needs n_micro % {S} == 0, got {M}")

    first_params, first_fn = first if first is not None else ({}, _identity_end)
    last_params, last_fn = last if last is not None else ({}, _identity_end)
    sfn = jax.checkpoint(stage_fn) if remat else stage_fn
    T = M * v + S - 1
    Mq = M // S  # feed rows per device (sharded mode)

    # Reshape stacked params [VS, ...] -> [v, S, ...] so P(None, axis) lands
    # chunk c of device d at leaf[c, 0] — virtual stage c*S + d, matching the
    # placement contract in the docstring.
    chunked = jax.tree.map(lambda x: x.reshape((v, S) + x.shape[1:]), stage_params)
    if feed == "sharded":
        # Strided layout: row [q, d] is microbatch q*S + d, so the rotating
        # one-slot feed ring below always finds microbatch m on device m % S.
        micro_in = microbatches.reshape((Mq, S) + microbatches.shape[1:])
        micro_spec = P(None, axis)
    else:
        micro_in = microbatches
        micro_spec = P()

    def body(local_chunks, local_micro, first_p, last_p):
        # Inside shard_map: local_chunks leaves are [v, 1, ...] (this device's
        # chunks); local_micro is [Mq, 1, mb, ...] (sharded) or [M, mb, ...]
        # (replicated).
        chunks = jax.tree.map(lambda x: x[:, 0], local_chunks)
        d = jax.lax.axis_index(axis)
        is_first = d == 0
        is_last = d == S - 1
        ring = [(i, (i + 1) % S) for i in range(S)]  # activation: d -> d+1
        feed_ring = [(i, (i - 1) % S) for i in range(S)]  # feed slot: d -> d-1

        if feed == "sharded":
            local_feed = jax.vmap(lambda m: first_fn(first_p, m))(local_micro[:, 0])
        else:
            local_feed = jax.vmap(lambda m: first_fn(first_p, m))(local_micro)
        act_shape = local_feed.shape[1:]
        act_dtype = local_feed.dtype

        def tick(carry, t):
            ring_in, slot, outputs = carry
            if feed == "sharded":
                # Refill every S ticks: device d loads the feed that must
                # reach device 0 at tick t+d (locally resident exactly then),
                # and the one-slot ring rotates it one hop per tick.
                qidx = jnp.clip((t + d) // VS, 0, Mq - 1)
                refill = jax.lax.dynamic_index_in_dim(local_feed, qidx, 0, keepdims=False)
                slot = jnp.where(t % S == 0, refill, slot)
                feed_now = slot
            else:
                m_t = (t // VS) * S + t % S  # device 0's feed schedule
                feed_now = jax.lax.dynamic_index_in_dim(
                    local_feed, jnp.clip(m_t, 0, M - 1), 0, keepdims=False
                )

            # Device-local schedule: active for M*v consecutive ticks from
            # t = d; chunk round-robin advances every S ticks.
            tau = t - d
            c = jnp.clip(tau // S, 0, M * v - 1) % v
            params_c = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, c, 0, keepdims=False), chunks
            )
            use_feed = jnp.logical_and(is_first, c == 0)
            x = jnp.where(use_feed, feed_now, ring_in)
            y = sfn(params_c, x)

            # Device S-1, chunk v-1 emits microbatch m = (e//VS)*S + e%VS at
            # e = t - (VS - 1); the strided residency means e%VS < S exactly
            # on emission ticks.
            e = t - (VS - 1)
            r = jnp.clip(e, 0, M * v - 1) % VS
            m_out = (jnp.clip(e, 0, M * v - 1) // VS) * S + r
            emit = jnp.logical_and(
                is_last, jnp.logical_and(e >= 0, jnp.logical_and(r < S, m_out < M))
            )
            idx = jnp.clip(m_out, 0, M - 1)
            cur = jax.lax.dynamic_slice_in_dim(outputs, idx, 1, 0)
            outputs = jax.lax.dynamic_update_slice_in_dim(
                outputs, jnp.where(emit, y[None], cur), idx, 0
            )

            sent = jax.lax.ppermute(y, axis, ring)
            if feed == "sharded":
                slot = jax.lax.ppermute(slot, axis, feed_ring)
            return (sent, slot, outputs), None

        # pcast-to-varying: the carry becomes device-varying after one tick
        # (each stage holds different activations), so the init must carry the
        # same varying-over-`axis` type or scan rejects the carry signature.
        def _vary(x):
            return pcast(x, axis, to="varying")

        init = (
            _vary(jnp.zeros(act_shape, act_dtype)),
            _vary(jnp.zeros(act_shape, act_dtype)),
            _vary(jnp.zeros((M,) + act_shape, act_dtype)),
        )
        (_, _, outputs), _ = jax.lax.scan(tick, init, jnp.arange(T))

        # Valid only on the last device; zero elsewhere so the psum below (or
        # the psum_scatter in the sharded-head path) recovers them exactly.
        outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        if last is not None and M % S == 0:
            # Sharded head: scatter the emitted outputs over the pipe group
            # (only the last device contributes, so the sum IS its value) and
            # apply `last` to M/S microbatches per device. The result stays
            # sharded — out_specs reassembles it without an in-body gather.
            mine = jax.lax.psum_scatter(
                outputs.reshape((Mq, S) + outputs.shape[1:]),
                axis,
                scatter_dimension=1,
                tiled=False,
            )
            done = jax.vmap(lambda m: last_fn(last_p, m))(mine)
            return done[:, None]  # [Mq, 1(sharded->S), mb, ...]
        outputs = jax.lax.psum(outputs, axis)
        if last is not None:
            outputs = jax.vmap(lambda m: last_fn(last_p, m))(outputs)
        return outputs

    sharded_head = last is not None and M % S == 0
    if stage_param_specs is not None:
        chunk_specs = jax.tree.map(
            lambda spec: P(None, axis, *spec), stage_param_specs
        )
    else:
        chunk_specs = jax.tree.map(lambda _: P(None, axis), chunked)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(chunk_specs, micro_spec, P(), P()),
        # Plain path: the closing psum establishes replication. Sharded-head
        # path: outputs stay sharded over `axis` on dim 1, reassembled below.
        out_specs=P(None, axis) if sharded_head else P(),
        # Manual over the pipe axis ONLY (plus any extra_manual_axes a stage
        # body needs hand-written collectives over): every other mesh axis
        # stays automatic, so stage bodies compose with the rest of the
        # matrix — activations sharded over `data`, MoE weights over
        # `expert`, TP over `model` — with GSPMD inserting those collectives
        # inside each tick while the ring ppermute stays hand-scheduled. On a
        # pipe-only mesh this is identical to full manual.
        axis_names=frozenset({axis, *extra_manual_axes}),
    )
    out = fn(chunked, micro_in, first_params, last_params)
    if sharded_head:
        # [Mq, S, mb, ...] with row [q, r] = microbatch q*S + r.
        out = out.reshape((M,) + out.shape[2:])
    return out
