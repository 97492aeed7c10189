"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2``) in plain float32
``jax.numpy``: learned positions, pre-LayerNorm blocks, fused qkv projection,
causal softmax attention, tanh-GELU MLP, tied output head, mean next-token
cross-entropy. No kernels, no cache, no batching tricks; each block is
rematerialised in the backward pass only so that a block of rows fits.

Parameter names and layouts are the published checkpoint's (``wte``, ``wpe``,
``h{i}.attn.c_attn.w`` as [d, 3d] with q | k | v side by side, ...).
``to_program`` is the only place that knows how the program lays them out.

Departure from the published config, because the program has no option for
it: LayerNorm epsilon is the configuration file's (1e-6, flax's default; the
published value is 1e-5), and the three dropouts are 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import HI, ROUNDERS


def param_shapes(cfg: dict, traffic: dict) -> dict:
    d, inner, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    positions = max(traffic["seq_len"], cfg["input"].get("min_positions", 1))
    shapes = {"wte": (v, d), "wpe": (positions, d), "ln_f.g": (d,), "ln_f.b": (d,)}
    for i in range(cfg["n_layer"]):
        h = f"h{i}."
        shapes.update({
            h + "ln_1.g": (d,), h + "ln_1.b": (d,),
            h + "attn.c_attn.w": (d, 3 * d), h + "attn.c_attn.b": (3 * d,),
            h + "attn.c_proj.w": (d, d), h + "attn.c_proj.b": (d,),
            h + "ln_2.g": (d,), h + "ln_2.b": (d,),
            h + "mlp.c_fc.w": (d, inner), h + "mlp.c_fc.b": (inner,),
            h + "mlp.c_proj.w": (inner, d), h + "mlp.c_proj.b": (d,),
        })
    return shapes


def init_params(cfg: dict, traffic: dict, key) -> dict:
    """GPT-2's published initialisation: N(0, 0.02) matrices and embeddings,
    residual projections scaled by 1/sqrt(2 * n_layer), zero biases, unit
    LayerNorm gains. One call, jit it: the weights are made on the device."""
    shapes = param_shapes(cfg, traffic)
    std = cfg["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name.endswith(".g"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(".b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            s = std / (2 * cfg["n_layer"]) ** 0.5 if name.endswith("c_proj.w") else std
            out[name] = s * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def to_program(params: dict, cfg: dict) -> dict:
    """The program's (flax) tree holding these values."""
    d, heads = cfg["n_embd"], cfg["n_head"]
    hd = d // heads
    tree = {
        "embed": {"embedding": params["wte"]},
        "pos_embed": params["wpe"][None],
        "LayerNorm_0": {"scale": params["ln_f.g"], "bias": params["ln_f.b"]},
    }
    for i in range(cfg["n_layer"]):
        h = f"h{i}."
        tree[f"DecoderBlock_{i}"] = {
            "LayerNorm_0": {"scale": params[h + "ln_1.g"], "bias": params[h + "ln_1.b"]},
            "qkv": {"kernel": params[h + "attn.c_attn.w"].reshape(d, 3, heads, hd),
                    "bias": params[h + "attn.c_attn.b"].reshape(3, heads, hd)},
            "attn_out": {"kernel": params[h + "attn.c_proj.w"].reshape(heads, hd, d),
                         "bias": params[h + "attn.c_proj.b"]},
            "LayerNorm_1": {"scale": params[h + "ln_2.g"], "bias": params[h + "ln_2.b"]},
            "mlp_in": {"kernel": params[h + "mlp.c_fc.w"], "bias": params[h + "mlp.c_fc.b"]},
            "mlp_out": {"kernel": params[h + "mlp.c_proj.w"], "bias": params[h + "mlp.c_proj.b"]},
        }
    return tree


def from_program(tree: dict, cfg: dict) -> dict:
    """The same leaves under the reference's names (shapes as the program has
    them: norms do not care)."""
    out = {
        "wte": tree["embed"]["embedding"], "wpe": tree["pos_embed"],
        "ln_f.g": tree["LayerNorm_0"]["scale"], "ln_f.b": tree["LayerNorm_0"]["bias"],
    }
    for i in range(cfg["n_layer"]):
        h, b = f"h{i}.", tree[f"DecoderBlock_{i}"]
        out.update({
            h + "ln_1.g": b["LayerNorm_0"]["scale"], h + "ln_1.b": b["LayerNorm_0"]["bias"],
            h + "attn.c_attn.w": b["qkv"]["kernel"], h + "attn.c_attn.b": b["qkv"]["bias"],
            h + "attn.c_proj.w": b["attn_out"]["kernel"], h + "attn.c_proj.b": b["attn_out"]["bias"],
            h + "ln_2.g": b["LayerNorm_1"]["scale"], h + "ln_2.b": b["LayerNorm_1"]["bias"],
            h + "mlp.c_fc.w": b["mlp_in"]["kernel"], h + "mlp.c_fc.b": b["mlp_in"]["bias"],
            h + "mlp.c_proj.w": b["mlp_out"]["kernel"], h + "mlp.c_proj.b": b["mlp_out"]["bias"],
        })
    return out


def leaves(tree: dict, cfg: dict) -> dict:
    """The leaves norms are taken over: the fused qkv projection split into
    its three tensors (the key's bias has no gradient under softmax and must
    be a leaf of its own to be left out by that rule). Takes either layout:
    the published [d, 3d] or the program's [d, 3, heads, head_dim]."""
    out = {}
    for name, x in tree.items():
        if ".attn.c_attn." not in name:
            out[name] = x
            continue
        if x.ndim in (1, 2):  # published: q | k | v side by side on the last axis
            parts = jnp.split(x, 3, axis=-1)
        else:  # program: an axis of 3 after the input axis
            axis = x.ndim - 3
            parts = [jnp.take(x, i, axis=axis) for i in range(3)]
        for tag, part in zip("qkv", parts, strict=True):
            out[name.replace("c_attn.", f"c_attn.{tag}.")] = part
    return out


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(x, p, heads, eps, rnd):
    b, t, d = x.shape
    hd = d // heads
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w), precision=HI)  # noqa: E731
    y = _layer_norm(x, p["ln_1.g"], p["ln_1.b"], eps)
    qkv = mm(y, p["attn.c_attn.w"]) + p["attn.c_attn.b"]
    q, k, v = (z.reshape(b, t, heads, hd).transpose(0, 2, 1, 3) for z in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", rnd(q), rnd(k), precision=HI) / hd**0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", rnd(probs), rnd(v), precision=HI)
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + mm(att, p["attn.c_proj.w"]) + p["attn.c_proj.b"]
    y = _layer_norm(x, p["ln_2.g"], p["ln_2.b"], eps)
    y = _gelu_new(mm(y, p["mlp.c_fc.w"]) + p["mlp.c_fc.b"])
    return x + mm(y, p["mlp.c_proj.w"]) + p["mlp.c_proj.b"]


def loss_sum(params: dict, batch: dict, cfg: dict, control=None):
    """Sum over the block's rows of the per-row mean next-token NLL (the
    caller divides by the step's rows)."""
    rnd = ROUNDERS[control]
    tokens, labels = batch["image"], batch["label"]
    t = tokens.shape[1]
    eps = cfg["layer_norm_epsilon"]
    x = params["wte"][tokens] + params["wpe"][:t]
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4))
    for i in range(cfg["n_layer"]):
        h = f"h{i}."
        p = {k[len(h):]: v for k, v in params.items() if k.startswith(h)}
        x = block(x, p, cfg["n_head"], eps, rnd)
    x = _layer_norm(x, params["ln_f.g"], params["ln_f.b"], eps)
    logits = jnp.matmul(rnd(x), rnd(params["wte"]).T, precision=HI)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(nll, axis=-1))
