"""VGG16 (Simonyan & Zisserman 2014, configuration D) as the reference
repository's ``model/vgg16.py`` has it, in plain float32 ``jax.numpy``: five
stages of 3x3 convolutions + ReLU each closed by a 2x2 max-pool, adaptive
average pool to 7x7, classifier 25088-4096-4096-classes, mean cross-entropy.
Pixels arrive as uint8 and are normalised with CIFAR-10's channel statistics.
Dropout is the configuration file's (0: see its ``reduced``).

Layouts are this file's own (NHWC, HWIO, classifier rows in (h, w, c)
order); ``to_program`` alone knows the program's tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import HI, ROUNDERS


def _convs(cfg):
    cin = 3
    for s, (feats, layers) in enumerate(zip(cfg["stage_features"], cfg["stage_layers"], strict=True)):
        for l in range(layers):
            yield s, l, cin, feats
            cin = feats


def param_shapes(cfg: dict, traffic: dict) -> dict:
    shapes = {}
    for s, l, cin, cout in _convs(cfg):
        shapes[f"conv{s}_{l}.w"] = (3, 3, cin, cout)
        shapes[f"conv{s}_{l}.b"] = (cout,)
    width = cfg["stage_features"][-1] * 49
    for i, out in enumerate((*cfg["classifier_widths"], cfg["num_classes"])):
        shapes[f"fc{i}.w"] = (width, out)
        shapes[f"fc{i}.b"] = (out,)
        width = out
    return shapes


def init_params(cfg: dict, traffic: dict, key) -> dict:
    """The reference repository's: Kaiming-normal (fan-out, ReLU) convolutions,
    N(0, 0.01) linear layers, zero biases."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg, traffic).items())):
        if name.endswith(".b"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        std = (2.0 / (9 * shape[-1])) ** 0.5 if name.startswith("conv") else 0.01
        out[name] = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def to_program(params: dict, cfg: dict) -> dict:
    inner = {}
    for s, l, _, _ in _convs(cfg):
        inner.setdefault(f"ConvBlock_{s}", {})[f"Conv_{l}"] = {
            "kernel": params[f"conv{s}_{l}.w"], "bias": params[f"conv{s}_{l}.b"]}
    for i in range(len(cfg["classifier_widths"]) + 1):
        inner[f"Dense_{i}"] = {"kernel": params[f"fc{i}.w"], "bias": params[f"fc{i}.b"]}
    return {"inner": inner}


def from_program(tree: dict, cfg: dict) -> dict:
    inner, out = tree["inner"], {}
    for s, l, _, _ in _convs(cfg):
        leaf = inner[f"ConvBlock_{s}"][f"Conv_{l}"]
        out[f"conv{s}_{l}.w"], out[f"conv{s}_{l}.b"] = leaf["kernel"], leaf["bias"]
    for i in range(len(cfg["classifier_widths"]) + 1):
        out[f"fc{i}.w"], out[f"fc{i}.b"] = inner[f"Dense_{i}"]["kernel"], inner[f"Dense_{i}"]["bias"]
    return out


def _adaptive_matrix(n_in: int, n_out: int) -> np.ndarray:
    """torch.nn.AdaptiveAvgPool1d: bin i averages [floor(i*n/o), ceil((i+1)*n/o))."""
    m = np.zeros((n_out, n_in), np.float32)
    for i in range(n_out):
        lo, hi = (i * n_in) // n_out, -(-((i + 1) * n_in) // n_out)
        m[i, lo:hi] = 1.0 / (hi - lo)
    return m


def loss_sum(params: dict, batch: dict, cfg: dict, control=None):
    """Sum over the block's rows of the cross-entropy."""
    rnd = ROUNDERS[control]
    mean = jnp.asarray(cfg["input"]["mean"], jnp.float32)
    std = jnp.asarray(cfg["input"]["std"], jnp.float32)
    x = (batch["image"].astype(jnp.float32) / 255.0 - mean) / std
    last_stage = 0
    for s, l, _, _ in _convs(cfg):
        if s != last_stage:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            last_stage = s
        x = jax.lax.conv_general_dilated(
            rnd(x), rnd(params[f"conv{s}_{l}.w"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI) + params[f"conv{s}_{l}.b"]
        x = jnp.maximum(x, 0.0)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    ph = jnp.asarray(_adaptive_matrix(x.shape[1], 7))
    pw = jnp.asarray(_adaptive_matrix(x.shape[2], 7))
    x = jnp.einsum("oh,bhwc->bowc", ph, x, precision=HI)
    x = jnp.einsum("pw,bowc->bopc", pw, x, precision=HI)
    x = x.reshape(x.shape[0], -1)
    n_fc = len(cfg["classifier_widths"]) + 1
    for i in range(n_fc):
        x = jnp.matmul(rnd(x), rnd(params[f"fc{i}.w"]), precision=HI) + params[f"fc{i}.b"]
        if i < n_fc - 1:
            x = jnp.maximum(x, 0.0)
    labels = batch["label"]
    nll = jax.nn.logsumexp(x, axis=-1) - jnp.take_along_axis(x, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll)
