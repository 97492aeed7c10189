"""What both plain references share: the matmul with its optional
lower-precision operand rounding (the control), and the plain optimizers.

Nothing here imports the program. Everything is float32 ``jax.numpy`` at
``highest`` matmul precision (on a TPU a float32 matmul otherwise runs as
one bfloat16 pass).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fp8_round(x):
    """Round to float8 e4m3 with a per-tensor scale, straight-through in the
    backward pass. The control: the nearest precision below the bfloat16 the
    configurations state, as an fp8 training recipe would apply it to every
    matmul operand."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def bf16_round(x):
    """The control of a configuration that states float32: bfloat16 operands."""
    return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


ROUNDERS = {None: lambda x: x, "fp8": fp8_round, "bf16": bf16_round}


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def leaves_view(ref):
    """``ref.leaves`` where a reference splits fused tensors for the norms, else the identity."""
    return getattr(ref, "leaves", lambda tree, _cfg: tree)


def schedule_lr(opt: dict, step, steps_per_epoch: int):
    """optax.warmup_cosine_decay_schedule as the entries build it."""
    s = opt["schedule"]
    total = max(2, s["total_epochs"] * steps_per_epoch)
    warm = max(1, min(s["warmup_epochs"] * steps_per_epoch, total - 1))
    peak = opt["lr"]
    step = jnp.asarray(step, jnp.float32)
    lin = peak * step / warm
    frac = jnp.clip((step - warm) / max(1, total - warm), 0.0, 1.0)
    cos = peak * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < warm, lin, cos)


def optimizer_init(params: dict, opt: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    if opt["kind"] == "adamw":
        return {"m": zeros, "v": {k: jnp.zeros_like(v) for k, v in params.items()}}
    if opt["kind"] == "sgd_momentum":
        return {"m": zeros}
    raise ValueError(f"unknown optimizer kind {opt['kind']!r}")


def optimizer_update(params: dict, grads: dict, state: dict, opt: dict, step: int, lr):
    """One plain update; ``step`` counts from 0. Returns (params, state)."""
    if opt["kind"] == "adamw":
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        t = step + 1
        m = {k: b1 * state["m"][k] + (1 - b1) * grads[k] for k in params}
        v = {k: b2 * state["v"][k] + (1 - b2) * jnp.square(grads[k]) for k in params}
        new = {}
        for k, p in params.items():
            m_hat = m[k] / (1 - b1**t)
            v_hat = v[k] / (1 - b2**t)
            new[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
        return new, {"m": m, "v": v}
    if opt["kind"] == "sgd_momentum":
        mom, wd = opt["momentum"], opt["weight_decay"]
        m = {k: grads[k] + wd * params[k] + mom * state["m"][k] for k in params}
        return {k: params[k] - lr * m[k] for k in params}, {"m": m}
    raise ValueError(f"unknown optimizer kind {opt['kind']!r}")
