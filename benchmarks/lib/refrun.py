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


def run_reference(ref, cfg: dict, traffic: dict, weight_seed: int, batches: list, *,
                  control=None, keep_rows: int | None = None, devices=None, watch=None) -> dict:
    """``weight_seed``: the start is ``ref.init_params`` from it, made here, so a
    second run (a stand-in) starts alike and no caller holds a tree.
    ``batches``: one ``{"image", "label"}`` of host arrays per step, as the
    device got them. ``control``: a lower precision for every matmul operand.
    ``keep_rows``: the planted fault "half of the batch left out, the mean
    taken over the rest". ``devices``: the cell's chips; over more than one, a
    call takes ``reference_block_rows`` rows a chip, split by row over them
    (the weights on each), so that a four-chip cell's four times larger batch
    takes the reference no longer than a one-chip cell's. ``watch`` is called
    between the jitted calls, where the device holds the most (the harness
    reads ``bytes_in_use`` there, the tests the live arrays). Returns per-step
    losses and, per compared leaf, the norms of the first gradient, of the
    optimizer's first moment after the last step and of the parameters'
    change over the steps.

    What the device holds beside a block's activations is at most 12 bytes a
    parameter, so that the reference fits where a configuration's training
    state filled the chip: the parameters and the gradient sum (8) with a
    block's gradients in ``accumulate`` (4), or with one leaf's optimizer
    state in ``update``. Between updates that state lives on the host; the
    start is made again by the call that made it, after the last moment is
    freed, and each leaf of it goes as its difference is taken."""
    opt = cfg["optimizer"]
    block_rows = traffic["reference_block_rows"]
    leaves = common.leaves_view(ref)
    watch = watch or (lambda: None)
    by_row = on_each = None
    if devices is not None and len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
        by_row = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows"))
        on_each = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        block_rows *= len(devices)
    # one compiled program makes the start, and makes it again at the end: the same bits
    init = functools.partial(jax.jit(lambda key: ref.init_params(cfg, traffic, key), out_shardings=on_each),
                             jax.random.key(weight_seed))

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(p, gsum, lsum, block):
        loss, grads = jax.value_and_grad(ref.loss_sum)(p, block, cfg, control)
        return jax.tree.map(jnp.add, gsum, grads), lsum + loss

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 2))
    def update(p, g, state, step, lr):  # one leaf: a program a shape and step, whatever the leaf's name
        new, state = common.optimizer_update({"x": p}, {"x": g}, {s: {"x": v} for s, v in state.items()}, opt, step, lr)
        return new["x"], {s: v["x"] for s, v in state.items()}

    params = init()
    held = {}  # the optimizer's state between updates, per leaf, on the host
    losses, grad1, moment = [], None, {}  # moment: the last step's first moments, left on the device
    for step, batch in enumerate(batches):
        rows = len(batch["label"]) if keep_rows is None else keep_rows
        gsum = jax.tree.map(jnp.zeros_like, params)
        lsum = jnp.zeros((), jnp.float32)
        for lo in range(0, rows, block_rows):
            block = {k: v[lo:min(lo + block_rows, rows)] for k, v in batch.items()}
            block = {k: jnp.asarray(v) if by_row is None else jax.device_put(v, by_row) for k, v in block.items()}
            gsum, lsum = accumulate(params, gsum, lsum, block)
            watch()
        grads = {k: gsum.pop(k) / rows for k in list(gsum)}
        losses.append(float(lsum) / rows)
        if grad1 is None:
            grad1 = _leaf_norms(leaves(grads, cfg))
        lr = common.schedule_lr(opt, step, traffic["steps_per_epoch"])
        last = step == len(batches) - 1
        for k in list(params):
            if step == 0:
                state = {s: v[k] for s, v in common.optimizer_init({k: params[k]}, opt).items()}
            else:
                state = {s: jnp.asarray(v) if on_each is None else jax.device_put(v, on_each)
                         for s, v in held.pop(k).items()}
            watch()
            params[k], state = update(params.pop(k), grads.pop(k), state, step, lr)
            # one leaf at a time: wait, or every leaf's state is on its way up at once
            if last:
                moment[k] = jax.block_until_ready(state["m"])
            else:
                held[k] = jax.device_get(state)
        watch()
    moment = _leaf_norms(leaves(moment, cfg))
    start = init()
    watch()
    delta = {k: params.pop(k) - start.pop(k) for k in list(params)}
    return {"losses": losses, "grad1": grad1, "moment": moment, "delta": _leaf_norms(leaves(delta, cfg))}


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
