"""Expert parallelism — a GShard-style Mixture-of-Experts FFN layer.

Not in the reference (data parallelism is its only strategy); built to
complete the parallelism matrix (dp / fsdp / tp / sp / pp / ep) the TPU way:
no per-expert processes or host-side routing — the layer is ordinary jittable
einsum algebra over an experts dimension, and *expert parallelism is purely a
sharding annotation*: stacked expert weights ``[E, ...]`` and the dispatched
``[E, capacity, d]`` activations carry ``PartitionSpec('expert', ...)``, and
XLA's SPMD partitioner inserts the all-to-all between the token-sharded and
expert-sharded layouts (the GShard formulation).

Routing: top-k softmax gating with fixed per-expert capacity. Tokens beyond
an expert's capacity are dropped for that choice (their other choice and the
residual path still carry them) — deterministic, order-based priority, first
choice before second. ``capacity_factor`` sizes the buffers.

Two dispatch implementations with identical routing semantics (parity-tested):

* ``dispatch_impl="einsum"`` — GShard one-hot dispatch/combine tensors
  ``[S/G, E, C]``; O((S/G)^2)-ish construction per group, all dense algebra.
  Best at small group sizes (the one-hots stay tiny and everything fuses).
* ``dispatch_impl="sort"`` — argsort/cummax ranking + scatter-add into the
  ``[E, C, d]`` buffers and gather back; memory and compute O(S·k + E·C·d)
  per group, no quadratic one-hots. Best at large group sizes. The
  crossover is not measured on today's chip.
* ``dispatch_impl="auto"`` (default) — picks per call site from the static
  group size: ``sort`` at >= :data:`SORT_DISPATCH_MIN_GROUP` tokens/group
  (an earlier round's ~4k crossover), ``einsum`` below. Group size is
  shape-derived, so the choice is made at trace time — no runtime branch
  under jit.

Inference: ``__call__(x, decode=True)`` routes capacity-free — every token
computes its top-k experts by direct weight gather (no buffers, no drops), the
standard MoE decode policy; identical parameters, so training checkpoints
serve decode unchanged.

Aux losses follow Switch/GShard: ``load_balance_loss`` (mean gate fraction x
mean dispatch fraction per expert, scaled by E) and ``router_z_loss``.

A second layer, :class:`HeldExpertsMlp` (end of the file), is what the
published sigmoid-routed models run on one expert-parallel rank: told which of
the published experts it holds, it routes over all of them, **drops nothing**
and computes its own experts' part of every token's sum through a grouped
product whose work follows the live rows. docs/parallelism.md sets the two
side by side.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from distributed_training_pytorch_tpu.parallel.mesh import DATA_AXIS, EXPERT_AXIS

__all__ = [
    "EXPERT_AXIS",
    "HeldExpertsMlp",
    "MoEMlp",
    "SORT_DISPATCH_MIN_GROUP",
    "held_rows",
    "load_balance_loss",
    "manual_expert_ffn_local",
    "manual_expert_mlp",
    "router_z_loss",
]

# einsum/sort crossover from an earlier round (E=8 k=2 d=512 h=1024 bf16);
# not measured on today's chip (ROADMAP D5/R1). "auto" flips to sort at this
# group size.
SORT_DISPATCH_MIN_GROUP = 4096


def _constrain(x: jax.Array, axes: tuple, *, activation: bool = False) -> jax.Array:
    """Constrain dims to mesh axes, skipping axes the ambient mesh lacks.

    No ambient mesh (plain apply outside jit, tests) -> no-op. With a mesh,
    genuine spec errors (e.g. expert count not divisible by the axis) DO
    propagate — silently dropping the constraint would run fully replicated
    while the user believes expert parallelism is active.

    ``activation=True`` marks dispatch/combine activation constraints, which
    are belt-and-braces: the expert-sharded WEIGHT constraints alone already
    make GSPMD shard the expert einsums. Inside a partial-manual region (a
    ``shard_map`` manual over e.g. ``pipe``, as ``pipeline_apply`` builds),
    activation constraints trip an XLA SPMD-partitioner CHECK
    (spmd_partitioner_util.cc "partition_group_list ... num_devices_per_group",
    bisected on jax 0.9/CPU) — so they are skipped there, and expert layout
    flows from the weights."""
    mesh = jax.sharding.get_abstract_mesh()
    mesh_axes = mesh.axis_names
    if not mesh_axes:
        return x
    if activation and mesh.manual_axes:
        return x
    spec = P(*[a if (a is not None and a in mesh_axes) else None for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)


def router_z_loss(logits: jax.Array) -> jax.Array:
    """Encourages small router logits (numerical health; ST-MoE eq. 5)."""
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z**2)


def load_balance_loss(gates: jax.Array, dispatch_mask: jax.Array) -> jax.Array:
    """Switch-Transformer load-balance loss: E * sum_e f_e * p_e where f_e is
    the fraction of tokens dispatched to expert e (first choice) and p_e the
    mean gate probability."""
    num_experts = gates.shape[-1]
    f = jnp.mean(dispatch_mask.astype(jnp.float32), axis=0)  # [E]
    p = jnp.mean(gates.astype(jnp.float32), axis=0)  # [E]
    return num_experts * jnp.sum(f * p)


def _route_group(group_gates, *, num_experts, capacity, top_k):
    """GShard order-based-capacity top-k routing for ONE group:
    ``[sg, E]`` gates -> ``(dispatch, combine, first_choice)`` with
    dispatch/combine ``[sg, E, C]``. Choices claim capacity in priority
    order (choice 0 of every token before any choice 1), so dropping is
    deterministic; kept gates renormalize to sum 1 per token."""
    e, sg = num_experts, group_gates.shape[0]
    remaining = group_gates
    dispatch = jnp.zeros((sg, e, capacity), jnp.bool_)
    combine = jnp.zeros((sg, e, capacity), jnp.float32)
    used = jnp.zeros((e,), jnp.int32)
    gate_sum = jnp.zeros((sg,), jnp.float32)
    first_choice = None
    for _ in range(top_k):
        choice = jnp.argmax(remaining, axis=-1)  # [sg]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [sg, E]
        if first_choice is None:
            first_choice = onehot
        pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # [sg, E]
        pos = jnp.sum(pos_in_expert * onehot, axis=-1) + used[choice]
        keep = pos < capacity
        gate = jnp.sum(group_gates * onehot, axis=-1) * keep
        slot = jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1), capacity, dtype=jnp.float32
        )
        contrib = onehot[:, :, None].astype(jnp.float32) * slot[:, None, :]
        contrib = contrib * keep[:, None, None]
        dispatch = jnp.logical_or(dispatch, contrib > 0)
        combine = combine + gate[:, None, None] * contrib
        gate_sum = gate_sum + gate
        used = used + jnp.sum(onehot * keep[:, None], axis=0)
        remaining = remaining * (1.0 - onehot)
    combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]
    return dispatch, combine, first_choice


class MoEMlp(nn.Module):
    """Mixture-of-experts FFN: ``[..., d] -> [..., d]``.

    Attributes:
      num_experts: E, ideally a multiple of the mesh's ``expert`` axis size.
      hidden_dim: per-expert FFN hidden width.
      top_k: experts per token (1 = Switch, 2 = GShard default).
      capacity_factor: per-expert buffer = ceil(group_tokens * top_k / E * factor).
      num_groups: routing groups (GShard's G). Dispatch/combine one-hots are
        O(S^2 * top_k / G); at training scale set this to the data-shard count
        so each shard routes its own tokens (buffers then shard over ``data``
        and stay O((S/G)^2)). Capacity is per group. S must divide by G.
      dtype: activation dtype (params stay float32).

    Sow'd metrics (``.sow('intermediates', ...)``): ``load_balance_loss`` and
    ``router_z_loss`` — add them to the training objective via the criterion.
    """

    num_experts: int
    hidden_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    num_groups: int = 1
    dispatch_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, *, decode: bool = False) -> jax.Array:
        orig_shape = x.shape
        d = orig_shape[-1]
        tokens = x.reshape(-1, d)  # [S, d]
        s = tokens.shape[0]
        e = self.num_experts
        g = self.num_groups
        if self.dispatch_impl not in ("auto", "einsum", "sort"):
            raise ValueError(
                f"dispatch_impl must be auto|einsum|sort, got {self.dispatch_impl!r}"
            )

        # --- router (float32 for stable softmax) ---------------------------
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32)
        )  # [S, E]
        gates = jax.nn.softmax(logits, axis=-1)

        # --- expert weights (expert-sharded) --------------------------------
        w_in = self.param(
            "w_in",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, d, self.hidden_dim),
            jnp.float32,
        )
        w_out = self.param(
            "w_out",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, self.hidden_dim, d),
            jnp.float32,
        )
        w_in = _constrain(w_in, (EXPERT_AXIS,)).astype(self.dtype)
        w_out = _constrain(w_out, (EXPERT_AXIS,)).astype(self.dtype)

        if decode:
            # Capacity-free inference routing: gather each token's top-k
            # expert weights and apply them directly — no buffers, no drops,
            # so per-step behavior matches training-renormalized gating
            # whenever training had capacity headroom. S is tiny at decode
            # (one token per sequence), so the [S, k, d, h] gather is cheap.
            gate_vals, choice = jax.lax.top_k(gates, self.top_k)  # [S, k]
            weights = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9
            )
            tk = tokens.astype(self.dtype)
            # jnp.take, not w_in[choice]: callers may pass host (numpy)
            # params outside jit, and numpy fancy-indexing rejects tracers.
            h = jax.nn.gelu(jnp.einsum("sd,skdh->skh", tk, jnp.take(w_in, choice, axis=0)))
            y = jnp.einsum("skh,skhd->skd", h, jnp.take(w_out, choice, axis=0))
            out = jnp.einsum("sk,skd->sd", weights.astype(self.dtype), y)
            return out.reshape(orig_shape).astype(self.dtype)

        if s % g:
            raise ValueError(f"{s} tokens not divisible by num_groups={g}")
        sg = s // g
        capacity = max(1, int(np.ceil(sg * self.top_k / e * self.capacity_factor)))
        # Resolve "auto" from the static group size (known at trace time).
        impl = self.dispatch_impl
        if impl == "auto":
            impl = "sort" if sg >= SORT_DISPATCH_MIN_GROUP else "einsum"

        # --- per-group top-k routing with order-based capacity --------------
        # Choices claim capacity in priority order (choice 0 of every token in
        # the group before any choice 1 — GShard policy) so dropping is
        # deterministic. Routing is vmapped over groups: one-hot buffers stay
        # O((S/G)^2) per group and shard over `data` with the groups.
        # (_route_group at module level — shared with manual_expert_mlp.)
        def route(group_gates):
            return _route_group(
                group_gates, num_experts=e, capacity=capacity, top_k=self.top_k
            )

        # Same routing semantics, scatter/gather instead of one-hot algebra:
        # rank each (choice, token) entry within its expert by a stable sort
        # (choice-major flattening preserves the GShard priority order), drop
        # ranks past capacity into a trash row, scatter-add into the [E, C, d]
        # buffers, and gather back weighted for the combine. No [sg, E, C]
        # tensors anywhere — O(sg*k) routing + O(E*C*d) buffers per group.
        n_flat = self.top_k * sg
        token_idx = jnp.tile(jnp.arange(sg), self.top_k)  # choice-major

        def route_sort(group_gates, group_tokens):
            gate_vals, choice = jax.lax.top_k(group_gates, self.top_k)  # [sg, k]
            ex_flat = choice.T.reshape(-1)  # [k*sg], choice-major
            order = jnp.argsort(ex_flat, stable=True)
            sorted_ex = ex_flat[order]
            arange = jnp.arange(n_flat)
            run_begin = jnp.where(
                jnp.concatenate([jnp.ones((1,), bool), sorted_ex[1:] != sorted_ex[:-1]]),
                arange,
                0,
            )
            pos_sorted = arange - jax.lax.cummax(run_begin)
            pos = jnp.zeros((n_flat,), jnp.int32).at[order].set(pos_sorted)
            keep = pos < capacity
            keep_tk = keep.reshape(self.top_k, sg).T  # [sg, k]
            gate_kept = gate_vals * keep_tk
            weight_tk = gate_kept / jnp.maximum(gate_kept.sum(-1, keepdims=True), 1e-9)
            rows = jnp.where(keep, ex_flat * capacity + pos, e * capacity)  # trash row
            buf = jnp.zeros((e * capacity + 1, d), self.dtype)
            buf = buf.at[rows].add(group_tokens.astype(self.dtype)[token_idx])
            expert_in = buf[:-1].reshape(e, capacity, d)
            first_choice = jax.nn.one_hot(choice[:, 0], e, dtype=jnp.int32)
            return expert_in, rows, weight_tk.T.reshape(-1), first_choice

        def combine_sort(expert_out, rows, w_flat):
            flat = expert_out.reshape(e * capacity, d)
            picked = flat[jnp.clip(rows, 0, e * capacity - 1)]
            picked = picked * (rows < e * capacity)[:, None]
            contrib = picked * w_flat.astype(self.dtype)[:, None]
            return jnp.zeros((sg, d), self.dtype).at[token_idx].add(contrib)

        grouped_gates = gates.reshape(g, sg, e)
        # The reshard from token-sharded [G over data] to expert-sharded IS
        # the all-to-all (inserted by the SPMD partitioner at the constraint).
        grouped_tokens = tokens.reshape(g, sg, d)
        grouped_tokens = _constrain(grouped_tokens, (DATA_AXIS,), activation=True)

        if impl == "sort":
            expert_in, rows, w_flat, first_choice = jax.vmap(route_sort)(
                grouped_gates, grouped_tokens
            )
        else:
            dispatch, combine, first_choice = jax.vmap(route)(grouped_gates)
            # dispatch: [G, sg, E, C] x [G, sg, d] -> [G, E, C, d]
            expert_in = jnp.einsum(
                "gsec,gsd->gecd",
                dispatch.astype(self.dtype),
                grouped_tokens.astype(self.dtype),
            )

        self.sow(
            "intermediates",
            "load_balance_loss",
            load_balance_loss(gates, first_choice.reshape(s, e)),
        )
        self.sow("intermediates", "router_z_loss", router_z_loss(logits))

        # --- expert computation (expert-sharded) ---------------------------
        expert_in = _constrain(expert_in, (DATA_AXIS, EXPERT_AXIS), activation=True)
        h = jax.nn.gelu(jnp.einsum("gecd,edh->gech", expert_in, w_in))
        expert_out = jnp.einsum("gech,ehd->gecd", h, w_out)
        expert_out = _constrain(expert_out, (DATA_AXIS, EXPERT_AXIS), activation=True)

        if impl == "sort":
            out = jax.vmap(combine_sort)(expert_out, rows, w_flat)
        else:
            out = jnp.einsum("gsec,gecd->gsd", combine.astype(self.dtype), expert_out)
        return out.reshape(orig_shape).astype(self.dtype)


def manual_expert_mlp(
    params,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    num_groups: int = 1,
    mesh=None,
    data_axis: str = DATA_AXIS,
    expert_axis: str = EXPERT_AXIS,
    exchange: str = "auto",
    dtype: Any = jnp.float32,
) -> jax.Array:
    """MoE FFN forward with expert parallelism expressed MANUALLY — the
    workaround for the data x expert x pipe composition.

    :class:`MoEMlp` expresses expert parallelism as sharding constraints and
    lets GSPMD insert the token<->expert all-to-all. Inside
    ``pipeline_apply``'s partial-manual region that path trips an upstream
    XLA SPMD-partitioner CHECK (``spmd_partitioner_util.cc``
    ``AllReduceAlongShardingDims``, repro: ``scripts/repro_triple_check.py``
    — a process-fatal CHECK, so it cannot live in pytest). This function
    sidesteps the partitioner entirely: a nested ``shard_map`` manual over
    ``(data, expert)`` whose body does the MoE exchange by hand, two
    formulations (``exchange=``):

    * ``"all_to_all"`` — the canonical GShard exchange: token groups shard
      jointly over ``(data, expert)``; each shard routes its own groups
      (:func:`_route_group`, the exact semantics of the einsum path), the
      ``[G_local, E, C, d]`` dispatch buffers swap experts<->groups with one
      ``jax.lax.all_to_all`` over ``expert``, the local slab runs its FFN,
      a second all_to_all returns the outputs, combine is local. Comm per
      device: 2 x buffer/n_exp. NOT usable inside an enclosing manual region
      whose free axis (``pipe``) sits between ``data`` and ``expert`` in the
      mesh order — Shardy rejects the joint dim sharding ("manual axis
      'expert' after free axis 'pipe'").
    * ``"psum"`` — groups shard over ``data`` only; routing replicates over
      the expert members, each applies its LOCAL expert slice of dispatch/
      combine, and one ``psum`` over ``expert`` sums the partial outputs
      (the :func:`manual_expert_ffn_local` formulation, runnable here
      un-nested for parity testing). Comm per device: one [tokens, d]
      all-reduce; prefer all_to_all.
    * ``"auto"`` (default) — all_to_all.

    NESTING: this function cannot run inside an enclosing ``shard_map``
    (pipeline_apply) at all — Shardy rejects both re-binding a parent's
    manual axis and an inner mesh differing from the context mesh — and
    raises a ValueError pointing at the supported composition:
    ``pipeline_apply(extra_manual_axes=("expert",), stage_param_specs=...)``
    with :func:`manual_expert_ffn_local` stage bodies.

    ``params``: an :class:`MoEMlp` ``variables["params"]`` tree (``router``
    Dense kernel/bias, ``w_in``, ``w_out``) — training checkpoints swap
    between the two implementations unchanged. ``x``: ``[..., d]``; token
    count must divide by ``num_groups``; ``num_groups`` by
    ``data_size * expert_size`` (all_to_all) or ``data_size`` (psum);
    ``num_experts`` by ``expert_size``. Differentiable; aux losses are not
    sow'd on this path (compute them from a separate router call if needed).
    """
    # Inside a traced context the shard_map must receive the ambient ABSTRACT
    # mesh (it carries e.g. pipe's Manual axis type from an enclosing
    # pipeline_apply region); the concrete mesh arg is the fallback for
    # un-nested use outside set_mesh.
    ctx = jax.sharding.get_abstract_mesh()
    if ctx.axis_names:
        mesh = ctx
    elif mesh is None:
        raise ValueError("manual_expert_mlp needs a mesh (arg or ambient set_mesh)")
    axis_names = getattr(mesh, "axis_names", ())
    n_exp = mesh.shape[expert_axis] if expert_axis in axis_names else 1
    n_data = mesh.shape[data_axis] if data_axis in axis_names else 1
    if exchange == "auto":
        exchange = "all_to_all"
    if exchange not in ("all_to_all", "psum"):
        raise ValueError(f"exchange must be all_to_all|psum|auto, got {exchange!r}")

    orig_shape = x.shape
    d = orig_shape[-1]
    tokens = x.reshape(-1, d)
    s = tokens.shape[0]
    g = num_groups
    e = num_experts
    if s % g:
        raise ValueError(f"{s} tokens not divisible by num_groups={g}")
    need = n_data * n_exp if exchange == "all_to_all" else n_data
    if g % need:
        raise ValueError(f"num_groups={g} must divide by {need} shards ({exchange})")
    if e % n_exp:
        raise ValueError(f"num_experts={e} not divisible by expert axis {n_exp}")
    sg = s // g
    capacity = max(1, int(np.ceil(sg * top_k / e * capacity_factor)))

    rk = params["router"]["kernel"]
    rb = params["router"]["bias"]
    w_in = params["w_in"]
    w_out = params["w_out"]
    grouped = tokens.reshape(g, sg, d)

    def body_a2a(grouped_local, rk, rb, w_in_local, w_out_local):
        # grouped_local: [G_local, sg, d]; w slabs: [E_local, d, h]/[E_local, h, d]
        dispatch, combine, _ = _route_grouped(
            grouped_local, rk, rb, num_experts=e, capacity=capacity, top_k=top_k
        )
        expert_in = jnp.einsum(
            "gsec,gsd->gecd", dispatch.astype(dtype), grouped_local.astype(dtype)
        )  # [G_local, E, C, d]
        if n_exp > 1:
            # experts -> groups exchange: split E into n_exp slabs, concat on
            # the group dim — each expert shard now holds ITS experts'
            # buffers for every group-set in this data row.
            expert_in = jax.lax.all_to_all(
                expert_in, expert_axis, split_axis=1, concat_axis=0, tiled=True
            )  # [G_local*n_exp, E_local, C, d]
        h = jax.nn.gelu(
            jnp.einsum("gecd,edh->gech", expert_in, w_in_local.astype(dtype))
        )
        expert_out = jnp.einsum("gech,ehd->gecd", h, w_out_local.astype(dtype))
        if n_exp > 1:
            expert_out = jax.lax.all_to_all(
                expert_out, expert_axis, split_axis=0, concat_axis=1, tiled=True
            )  # [G_local, E, C, d]
        out = jnp.einsum("gsec,gecd->gsd", combine.astype(dtype), expert_out)
        return out

    def body_psum(grouped_local, rk, rb, w_in_local, w_out_local):
        params_local = {
            "router": {"kernel": rk, "bias": rb},
            "w_in": w_in_local,
            "w_out": w_out_local,
        }
        return manual_expert_ffn_local(
            params_local, grouped_local,
            num_experts=e, n_expert_shards=n_exp, expert_axis=expert_axis,
            top_k=top_k, capacity=capacity, dtype=dtype,
        )

    if mesh.manual_axes:
        raise ValueError(
            "manual_expert_mlp cannot nest inside an enclosing shard_map "
            "(Shardy rejects both re-binding a parent's manual axis and a "
            "sub-mesh that differs from the context mesh). Inside "
            "pipeline_apply, pass extra_manual_axes=('expert',) + "
            "stage_param_specs and call moe.manual_expert_ffn_local from the "
            "stage body instead."
        )
    # Specs reference only axes the mesh actually has — degenerate meshes
    # (no expert axis, or no data axis) run the same bodies with the
    # collectives compiled out (`if n_exp > 1` guards).
    def _present(*axes):
        return P(tuple(a for a in axes if a in axis_names) or None)

    w_spec = _present(expert_axis)
    if exchange == "all_to_all":
        body, x_spec = body_a2a, _present(data_axis, expert_axis)
    else:
        body, x_spec = body_psum, _present(data_axis)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(), P(), w_spec, w_spec),
        out_specs=x_spec,
        axis_names=frozenset(a for a in (data_axis, expert_axis) if a in axis_names),
    )
    out = fn(grouped, rk, rb, w_in, w_out)
    return out.reshape(orig_shape).astype(dtype)


def _route_grouped(grouped, rk, rb, *, num_experts, capacity, top_k):
    """Router + per-group GShard routing over ``[G, sg, d]`` tokens."""
    logits = grouped.astype(jnp.float32) @ rk + rb  # [G, sg, E]
    gates = jax.nn.softmax(logits, axis=-1)
    return jax.vmap(
        lambda gg: _route_group(
            gg, num_experts=num_experts, capacity=capacity, top_k=top_k
        )
    )(gates)


def manual_expert_ffn_local(
    params_local,
    grouped: jax.Array,
    *,
    num_experts: int,
    n_expert_shards: int,
    expert_axis: str = EXPERT_AXIS,
    top_k: int = 2,
    capacity: int | None = None,
    capacity_factor: float = 1.25,
    dtype: Any = jnp.float32,
) -> jax.Array:
    """Expert-parallel MoE FFN for use INSIDE an already-manual region over
    ``expert_axis`` — the stage-body half of the data x expert x pipe
    workaround (``pipeline_apply(extra_manual_axes=("expert",), ...)``).

    ``params_local``: MoEMlp-layout params whose ``w_in``/``w_out`` are this
    shard's LOCAL ``[E/n, d, h]`` slabs (the region's in_specs sliced them);
    router kernel/bias replicated. ``grouped``: ``[G, sg, d]`` tokens,
    replicated over ``expert_axis``. Routing replicates across expert
    members (:func:`_route_group` semantics — identical to the einsum path);
    each member applies its local expert slice of dispatch/combine and one
    ``psum`` over ``expert_axis`` sums the partial outputs."""
    e = num_experts
    n_exp = n_expert_shards
    if capacity is None:
        sg = grouped.shape[1]
        capacity = max(1, int(np.ceil(sg * top_k / e * capacity_factor)))
    rk = params_local["router"]["kernel"]
    rb = params_local["router"]["bias"]
    dispatch, combine, _ = _route_grouped(
        grouped, rk, rb, num_experts=e, capacity=capacity, top_k=top_k
    )
    e_loc = e // n_exp
    start = (
        jax.lax.axis_index(expert_axis) * e_loc if n_exp > 1 else jnp.zeros((), jnp.int32)
    )
    disp_l = jax.lax.dynamic_slice_in_dim(dispatch.astype(dtype), start, e_loc, 2)
    comb_l = jax.lax.dynamic_slice_in_dim(combine.astype(dtype), start, e_loc, 2)
    expert_in = jnp.einsum("gsec,gsd->gecd", disp_l, grouped.astype(dtype))
    h = jax.nn.gelu(
        jnp.einsum("gecd,edh->gech", expert_in, params_local["w_in"].astype(dtype))
    )
    expert_out = jnp.einsum(
        "gech,ehd->gecd", h, params_local["w_out"].astype(dtype)
    )
    out = jnp.einsum("gsec,gecd->gsd", comb_l, expert_out)
    if n_exp > 1:
        out = jax.lax.psum(out, expert_axis)
    return out


# ---------------------------------------------------------------------------
# Dropless routing over a chip's share of the experts
# ---------------------------------------------------------------------------
#
# ``MoEMlp`` above sizes a buffer an expert and drops what overflows it. The
# layer below drops nothing and is told which experts it holds: the unit an
# expert-parallel rank runs between its two exchanges (which this file does
# not have yet: ROADMAP M2), and what one chip's share of a published model
# is (docs/parallelism.md). Its pairs' buffer has a row for every (token,
# choice) pair, of which the held experts fill a few per cent; everything the
# layer does to rows follows the live ones where it can: the grouped products
# (``jax.lax.ragged_dot``) everywhere, the four row movements around them
# (``_rows_in``, ``_rows_out`` and their written transposes) where the kernels of
# ``ops/moe_rows.py`` run.


def held_rows(top, held_first: int, held_count: int):
    """Where each (token, choice) pair of ``top`` (``[N, k]`` expert ids over
    the published count) goes in a buffer of ``N·k`` rows that holds the pairs
    of experts ``held_first … held_first + held_count − 1`` at its front,
    expert by expert and in token order inside an expert (a counting sort: a
    running count a held expert, no comparison sort). Returns ``dest`` ``[N,
    k]`` (a pair's row; 0 where the pair is routed elsewhere), ``live`` ``[N,
    k]`` (the pair is held here), ``src`` ``[N·k]`` (a row's pair, token-major;
    0 past the live rows) and ``sizes`` ``[held_count]`` (rows an expert)."""
    n, k = top.shape
    local = top - held_first
    live = (local >= 0) & (local < held_count)
    one_hot = (live[..., None] & (local[..., None] == jnp.arange(held_count))).reshape(n * k, held_count).astype(jnp.int32)
    before = jnp.cumsum(one_hot, axis=0) - one_hot  # pairs of the same expert that come first
    sizes = jnp.sum(one_hot, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    dest = jnp.sum(one_hot * (starts + before), axis=-1)
    nowhere = n * k  # out of range: dropped by the scatter below
    src = jnp.zeros((n * k,), jnp.int32).at[jnp.where(live.reshape(-1), dest, nowhere)].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop", unique_indices=True)
    return dest.reshape(n, k), live, src, sizes


class Route(NamedTuple):
    """A layer's routing as the four row movements read it: :func:`held_rows`'
    ``dest``, ``live`` and ``src``, the live count, and (where the kernels of
    ``ops/moe_rows.py`` run, else None) its ``live_pairs``."""

    dest: Any
    live: Any
    src: Any
    n_live: Any
    starts: Any = None
    pair: Any = None


# The four row movements. Each has two forms and takes ``tile`` to say which:
# None is the ``jax.numpy`` form, which works over all ``N·k`` pairs and is the
# definition; a number is the tokens a grid step of the kernels of
# ``ops/moe_rows.py``, whose work follows the ``n_live`` live pairs
# (``ops/dispatch.py:moe_rows_tile`` picks and records). What the buffer's rows
# past the live ones hold: in the ``jax.numpy`` form copies of token 0
# (``_rows_in``) and zeros (``d_rows``); from the kernels zeros up to the end
# of the last live tile and, past it, **whatever the allocation held, NaN
# included**. No one may read them unmasked, and no one does: the grouped
# products and both of their transposes stop at the groups' last row, relu² and
# its gradient work a row at a time, and the movements back read live rows only
# (``tests/test_moe.py`` poisons them; ``chip_smoke.py`` does on the chip).


def _pairs_rows(rows, dest, live):
    """``[N, k, d]``: each pair's row of the buffer, zeros for a pair held
    elsewhere (it points at row 0 and is masked: what a dead row holds is no one's)."""
    return jnp.where(live[..., None], rows[dest], 0)


def _kernels():
    """``ops/moe_rows.py``, imported where a layer first takes its kernels (it brings Pallas with it)."""
    from distributed_training_pytorch_tpu.ops import moe_rows

    return moe_rows


def _tokens_from_rows(tile, rows, route, weights, out_dtype):
    return _kernels().tokens_from_rows(rows, route.starts, route.pair, route.dest, weights, out_dtype=out_dtype, tile=tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_in(tile, x, route):
    """``[N, d] -> [N·k, d]``: row ``r`` is the token of pair ``src[r]``. Its
    transpose is written as the gather it is (a token sums its live pairs'
    rows), not the scatter-add autodiff would emit."""
    k = route.dest.shape[1]
    if tile is None:
        return x[route.src // k]
    return _kernels().rows_from_table(x, route.src, route.n_live, k=k)


def _rows_in_fwd(tile, x, route):
    return _rows_in(tile, x, route), route


def _rows_in_bwd(tile, route, d_rows):
    # a custom_vjp's backward half does not inherit the scopes of its call site: name them here for the trace's readers
    with jax.named_scope("moe_layer"), jax.named_scope("moe_dispatch"):
        if tile is None:
            dx = jnp.sum(_pairs_rows(d_rows, route.dest, route.live).astype(jnp.float32), axis=1).astype(d_rows.dtype)
        else:
            dx = _tokens_from_rows(tile, d_rows, route, None, d_rows.dtype)
        return dx, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_out(tile, rows, weights, route):
    """``out[t] = Σ_j weights[t, j] · rows[dest[t, j]]`` over the live pairs,
    float32: the combine. Rows past the live ones are never read unmasked."""
    if tile is None:
        return jnp.sum(weights[..., None] * _pairs_rows(rows, route.dest, route.live).astype(jnp.float32), axis=1)
    return _tokens_from_rows(tile, rows, route, weights, jnp.float32)


def _rows_out_fwd(tile, rows, weights, route):
    return _rows_out(tile, rows, weights, route), (rows, weights, route)


def _rows_out_bwd(tile, res, d_out):
    rows, weights, route = res
    dest, live, src = route.dest, route.live, route.src
    k = dest.shape[1]
    with jax.named_scope("moe_layer"), jax.named_scope("moe_combine"):
        if tile is None:
            is_live = (jnp.arange(src.shape[0]) < route.n_live)[:, None]
            d_rows = jnp.where(is_live, weights.reshape(-1)[src][:, None] * d_out[src // k], 0).astype(rows.dtype)
            d_weights = jnp.sum(_pairs_rows(rows, dest, live).astype(jnp.float32) * d_out[:, None, :], axis=-1)
        else:  # the same visit has a row and its token's ``d_out`` in VMEM: their product goes to the row's pair
            d_rows, d_weights = _kernels().rows_from_table(d_out, src, route.n_live, weights, rows, k=k, out_dtype=rows.dtype)
        return d_rows, d_weights.astype(weights.dtype), None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


def _relu2(x):
    return jnp.square(nn.relu(x))


class HeldExpertsMlp(nn.Module):
    """Dropless top-k routing over ``experts_published`` experts, computed for
    the ``held_count`` of them this chip holds (``held_first`` on), plus a
    shared expert every token passes through.

    Per token ``x`` (the caller's float32 norm output; the router stays in
    float32, as the published ``nemotron_h`` / DeepSeek-V3 routers do)::

        s = sigmoid(x W_rᵀ)                        W_r [experts_published, d]
        top = the top_k largest of s + b_corr      (b_corr selects only: no gradient reaches it)
        w_e = routed_scaling · s_e / (Σ_{e' ∈ top} s_{e'} + 1e-20)
        out = Σ_{e ∈ top, e held here} w_e · f_e(x)  +  f_shared(x),   f(x) = relu(x U)² V

    A pair routed to an expert held elsewhere contributes nothing here: that
    partial sum is one expert-parallel rank's, and the ranks' sums (the shared
    expert counted once) add up to the whole layer (``tests/test_moe.py``).
    **No pair is dropped**: the (token, choice) pairs of the held experts are
    sorted to the front of a static ``[tokens · top_k, d]`` buffer
    (:func:`held_rows`), which holds every pair even if all are routed here,
    and the two products are ``jax.lax.ragged_dot`` over the held experts'
    groups of rows: on a TPU the compiler lowers it (and both of its
    transposes) to its own Mosaic kernel with a scalar-prefetched table of the
    row tiles that hold a live row (``ragged-dot-metadata`` in the compiled
    step), so the work follows the live rows and not the static buffer, which
    a dense product a group or a capacity-padded einsum would not (PERF.md
    section 6, PR 36, has the timings on the chip; the Pallas ``megablox``
    kernel jax ships was not tried against it). The rows' way into the
    buffer and back (dispatch, combine) and the transposes of both take one
    of two forms, which ``ops/dispatch.py:moe_rows_tile`` picks from what it
    can see and records as ``(model, "moe_rows", "pallas" | "gather", reason)``:
    on a TPU, where ``d`` is whole 128-lane registers, the tokens whole tiles
    and on one device, the two kernels of ``ops/moe_rows.py``, which copy, scale
    and sum one row a live pair (``moe_pairs_local`` of them: 3% of the buffer
    in ``nemotron3nano_t8192``) and visit no tile of the buffer past the last
    live one; anywhere else gathers and masked sums over all ``tokens · top_k``
    pairs in ``jax.numpy``, which are the definition the kernels are held to
    (``tests/test_moe.py``). Both keep the router and the weights float32, sum
    a token's pairs in float32 and round to ``dtype`` at the same two places
    (``d_rows``, the dispatch's ``dx``). **Rows past the live ones hold nothing
    a caller may read**: copies of token 0 or zeros in the ``jax.numpy`` form,
    zeros to the end of the last live tile and past it whatever the
    allocation held from the kernels (the comment above ``_pairs_rows``).
    The products' one path is recorded too (``moe_experts``). ``dtype`` is what the experts'
    matmuls compute in. Sows ``moe_pairs_local`` (live pairs) and
    ``moe_pairs_max_expert`` (the fullest held expert's) into ``intermediates``.
    Scopes: ``moe_layer`` › ``moe_router``, ``moe_dispatch``, ``moe_experts``,
    ``moe_combine``, ``shared_expert``."""

    expert_width: int
    shared_width: int
    experts_published: int
    held_first: int
    held_count: int
    top_k: int
    routed_scaling: float = 1.0
    dtype: Any = jnp.float32
    model: str = "moe"  # whose ``kernel_dispatch`` record the experts' product is

    @nn.compact
    def __call__(self, x):
        from distributed_training_pytorch_tpu.ops import dispatch

        if not 0 <= self.held_first <= self.held_first + self.held_count <= self.experts_published:
            raise ValueError(f"experts {self.held_first}..{self.held_first + self.held_count - 1} are not among "
                             f"the {self.experts_published} published")
        lead, d = x.shape[:-1], x.shape[-1]
        init = nn.initializers.normal(stddev=0.02)
        with jax.named_scope("moe_layer"):
            x32 = x.reshape(-1, d).astype(jnp.float32)
            xc = x32.astype(self.dtype)
            with jax.named_scope("moe_router"):
                w_router = self.param("router", init, (self.experts_published, d), jnp.float32)
                b_corr = self.param("score_correction_bias", nn.initializers.zeros, (self.experts_published,), jnp.float32)
                scores = jax.nn.sigmoid(jnp.einsum("td,ed->te", x32, w_router, precision=jax.lax.Precision.HIGHEST))
            with jax.named_scope("moe_dispatch"):
                _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(b_corr), self.top_k)
                chosen = jnp.take_along_axis(scores, top, axis=-1)
                weights = self.routed_scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
                dest, live, src, sizes = held_rows(top, self.held_first, self.held_count)
                tile = dispatch.moe_rows_tile(self.model, dest.shape[0], self.top_k, d, self.dtype)
                route = Route(dest, live, src, jnp.sum(sizes), *(_kernels().live_pairs(live, tile) if tile is not None else ()))
                rows = _rows_in(tile, xc, route)
            self.sow("intermediates", "moe_pairs_local", jnp.sum(sizes).astype(jnp.float32))
            self.sow("intermediates", "moe_pairs_max_expert", jnp.max(sizes).astype(jnp.float32))
            with jax.named_scope("moe_experts"):
                up = self.param("experts_up", init, (self.held_count, d, self.expert_width), jnp.float32)
                down = self.param("experts_down", init, (self.held_count, self.expert_width, d), jnp.float32)
                dispatch.record(self.model, "moe_experts", "ragged_dot",
                                reason=f"backend={jax.default_backend()}: jax.lax.ragged_dot over {self.held_count} held experts; "
                                       "the compiler's grouped-matmul kernel on a TPU (live row tiles only), jax's own lowering elsewhere")
                hidden = _relu2(jax.lax.ragged_dot(rows, up.astype(self.dtype), sizes, preferred_element_type=self.dtype))
                rows = jax.lax.ragged_dot(hidden, down.astype(self.dtype), sizes, preferred_element_type=self.dtype)
            with jax.named_scope("moe_combine"):
                out = _rows_out(tile, rows, weights, route)
            with jax.named_scope("shared_expert"):
                dense = lambda width, name: nn.Dense(width, use_bias=False, dtype=self.dtype, kernel_init=init, name=name)  # noqa: E731
                shared = dense(d, "shared_down")(_relu2(dense(self.shared_width, "shared_up")(xc)))
            return (out + shared.astype(jnp.float32)).astype(self.dtype).reshape(lead + (d,))
