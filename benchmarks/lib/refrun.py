"""Drive a plain reference through the first optimizer steps, a block of
rows at a time so that it fits beside nothing else on the chip."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import common


def _leaf_norms(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(common.leaf_norms(tree)).items()}


def run_reference(ref, cfg: dict, traffic: dict, params: dict, batches: list, *,
                  control=None, keep_rows: int | None = None, devices=None) -> dict:
    """``batches``: one ``{"image", "label"}`` of host arrays per step, as the
    device got them. ``control``: a lower precision for every matmul operand.
    ``keep_rows``: the planted fault "half of the batch left out, the mean
    taken over the rest". ``devices``: the cell's chips; over more than one, a
    call takes ``reference_block_rows`` rows a chip, split by row over them
    (the weights on each), so that a four-chip cell's four times larger batch
    takes the reference no longer than a one-chip cell's. Returns per-step
    losses and, per compared leaf, the norms of the first gradient, of the
    optimizer's first moment after the last step and of the parameters'
    change over the steps."""
    opt = cfg["optimizer"]
    block_rows = traffic["reference_block_rows"]
    leaves = common.leaves_view(ref)
    by_row = None
    if devices is not None and len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
        by_row = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows"))
        params = jax.device_put(params, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        block_rows *= len(devices)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(p, gsum, lsum, block):
        loss, grads = jax.value_and_grad(ref.loss_sum)(p, block, cfg, control)
        return jax.tree.map(jnp.add, gsum, grads), lsum + loss

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 2))
    def update(p, grads, state, step, lr):
        return common.optimizer_update(p, grads, state, opt, step, lr)

    start, params = params, jax.tree.map(jnp.copy, params)  # the caller's buffers are never donated
    state = common.optimizer_init(params, opt)
    losses, grad1 = [], None
    for step, batch in enumerate(batches):
        rows = len(batch["label"]) if keep_rows is None else keep_rows
        gsum = jax.tree.map(jnp.zeros_like, params)
        lsum = jnp.zeros((), jnp.float32)
        for lo in range(0, rows, block_rows):
            block = {k: v[lo:min(lo + block_rows, rows)] for k, v in batch.items()}
            block = {k: jnp.asarray(v) if by_row is None else jax.device_put(v, by_row) for k, v in block.items()}
            gsum, lsum = accumulate(params, gsum, lsum, block)
        grads = jax.tree.map(lambda g: g / rows, gsum)
        losses.append(float(lsum) / rows)
        if grad1 is None:
            grad1 = _leaf_norms(leaves(grads, cfg))
        lr = common.schedule_lr(opt, step, traffic["steps_per_epoch"])
        params, state = update(params, grads, state, step, lr)
    delta = jax.tree.map(jnp.subtract, params, start)
    return {
        "losses": losses,
        "grad1": grad1,
        "moment": _leaf_norms(leaves(state["m"], cfg)),
        "delta": _leaf_norms(leaves(delta, cfg)),
    }


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers `correct` compares. Losses: the widest relative gap over
    the steps. Norms: by the worst leaf, the gap between the program's norm
    and the reference's over the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves whose first gradient in the
    reference is under a thousandth of the median leaf's (a key's bias under
    softmax) move under Adam by round-off alone and are left out of the
    change."""
    out = {}
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"program took {lp.shape} recorded steps, reference {lr.shape}")
    with np.errstate(invalid="ignore"):
        loss_gap = np.abs(lp - lr) / np.abs(lr)
    out["loss_gap"] = float(np.max(np.where(np.isfinite(loss_gap), loss_gap, np.inf)))
    g_med = float(np.median(list(ref["grad1"].values())))
    live = [k for k, v in ref["grad1"].items() if v >= 1e-3 * g_med]
    for name, key, names in (("grad_gap", "moment", list(ref["moment"])), ("delta_gap", "delta", live)):
        med = float(np.median([ref[key][k] for k in names]))
        worst, worst_leaf = 0.0, None
        for k in names:
            p = prog[key][k]
            gap = abs(p - ref[key][k]) / max(ref[key][k], med) if np.isfinite(p) else np.inf
            if gap >= worst:
                worst, worst_leaf = gap, k
        out[name] = float(worst)
        out[name + "_leaf"] = worst_leaf
    out["leaves_left_out"] = sorted(set(ref["grad1"]) - set(live))
    return out
