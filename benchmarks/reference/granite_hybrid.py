"""The ``granitemoehybrid`` stack without routed experts
(``ibm-granite/granite-4.0-h-micro``) in plain float32 ``jax.numpy``: Mamba-2
layers and grouped-query attention layers by ``layer_types``, one shared gated
MLP after each, RMSNorm, no positions, tied head, Granite's four multipliers,
mean next-token cross-entropy. The state-space layer is the **sequential
recurrence over time** (a ``lax.scan`` of T steps), not the chunked dual form
the program runs; attention is a masked softmax over the whole [T, T] square,
a key/value head at a time with its group of query heads; the logits are the
full [T, V]. No kernel, no cache. Recomputation only so that a block of rows
fits beside the weights: every layer is rematerialised, and inside it each
stretch of ``SEGMENT`` steps of the time scan and each key/value group.

Equations, per token, ``n(x) = x / sqrt(mean(x²) + eps) * w``::

    h⁰ = embedding_multiplier · E[id]
    h ← h + residual_multiplier · mixer_l(n₁(h));  h ← h + residual_multiplier · mlp(n₂(h))
    logits = n_f(h) Eᵀ / logits_scaling
    mlp:        [a | b] = x W_in;  y = (silu(a) ⊙ b) W_out
    attention:  softmax(attention_multiplier · q kᵀ, causal) v; key/value head g serves
                query heads g·r … g·r + r − 1 (r = heads / kv heads)
    mamba:      [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC)), conv(s)_t = b + Σ_j w_j ⊙ s_{t-K+1+j};
                [x | B | C] = xBC;  Δ = softplus(dt + dt_bias);  A = −exp(A_log);
                S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ (S_0 = 0);  y_t = S_t C_t + D x_t;
                out = n_g(y ⊙ silu(z)) W_out

Names follow the published checkpoint's modules (``layers.{i}.mamba.in_proj``,
``shared_mlp.input_linear``, ``self_attn.q_proj`` …); every matrix is stored
[in, out]. ``to_program`` is the only place that knows the program's tree.
Nothing here imports the program.

Not in the published config, so assumed (the configuration file lists them):
the initialisation (N(0, ``initializer_range``) matrices, unit norms, and
Mamba-2's own for what is the mixer's alone: conv taps U(±1/sqrt(K)), the
fan-in rule its ``nn.Conv1d`` is left at, zero conv bias, ``A_log = log U[1,
16]``, ``dt_bias`` the inverse softplus of a log-uniform Δ on [1e-3, 1e-1],
``D = 1``; with taps of N(0, 0.02) the scan would give 0.04% of the mixer's
output against 12%, and no comparison could see it) and the order ``[z | xBC | dt]`` of
``in_proj``'s columns (``transformers``' ``GraniteMoeHybridMambaLayer``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import HI, ROUNDERS

SEGMENT = 64  # steps of the time scan rematerialised together: a step saves one [heads, 64, 128] state


def _dims(cfg: dict) -> dict:
    d_inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return {"d": cfg["hidden_size"], "d_inner": d_inner, "n": cfg["mamba_d_state"],
            "conv_dim": d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
            "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"]}


def param_shapes(cfg: dict, traffic: dict) -> dict:
    s = _dims(cfg)
    d, inner = s["d"], cfg["shared_intermediate_size"]
    shapes = {"embed_tokens": (cfg["vocab_size"], d), "norm.w": (d,)}
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{i}."
        shapes.update({pre + "input_layernorm.w": (d,), pre + "post_attention_layernorm.w": (d,),
                       pre + "shared_mlp.input_linear.w": (d, 2 * inner), pre + "shared_mlp.output_linear.w": (inner, d)})
        if kind == "attention":
            shapes.update({pre + "self_attn.q_proj.w": (d, s["heads"] * s["hd"]),
                           pre + "self_attn.k_proj.w": (d, s["kv_heads"] * s["hd"]),
                           pre + "self_attn.v_proj.w": (d, s["kv_heads"] * s["hd"]),
                           pre + "self_attn.o_proj.w": (s["heads"] * s["hd"], d)})
        else:
            h = cfg["mamba_n_heads"]
            shapes.update({pre + "mamba.in_proj.w": (d, s["d_inner"] + s["conv_dim"] + h),
                           pre + "mamba.conv1d.w": (cfg["mamba_d_conv"], s["conv_dim"]),
                           pre + "mamba.conv1d.b": (s["conv_dim"],),
                           pre + "mamba.dt_bias": (h,), pre + "mamba.A_log": (h,), pre + "mamba.D": (h,),
                           pre + "mamba.norm.w": (s["d_inner"],), pre + "mamba.out_proj.w": (s["d_inner"], d)})
    return shapes


def init_params(cfg: dict, traffic: dict, key) -> dict:
    """The initialisation the configuration file lists under ``assumed``.
    One call, jit it: the weights are made on the device."""
    std = cfg["assumed"]["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg, traffic).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith(("norm.w", ".D")):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("conv1d.b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        elif name.endswith("conv1d.w"):
            bound = cfg["mamba_d_conv"] ** -0.5
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name.endswith("A_log"):
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("dt_bias"):
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
    return out


# reference name within a layer -> path in the program's block
_BLOCK = {
    "input_layernorm.w": ("input_layernorm", "scale"), "post_attention_layernorm.w": ("post_attention_layernorm", "scale"),
    "shared_mlp.input_linear.w": ("mlp_in", "kernel"), "shared_mlp.output_linear.w": ("mlp_out", "kernel"),
    "self_attn.q_proj.w": ("self_attn", "q_proj", "kernel"), "self_attn.k_proj.w": ("self_attn", "k_proj", "kernel"),
    "self_attn.v_proj.w": ("self_attn", "v_proj", "kernel"), "self_attn.o_proj.w": ("self_attn", "o_proj", "kernel"),
    "mamba.in_proj.w": ("mamba", "in_proj", "kernel"), "mamba.conv1d.w": ("mamba", "conv_kernel"),
    "mamba.conv1d.b": ("mamba", "conv_bias"), "mamba.dt_bias": ("mamba", "dt_bias"), "mamba.A_log": ("mamba", "A_log"),
    "mamba.D": ("mamba", "D"), "mamba.norm.w": ("mamba", "norm", "scale"), "mamba.out_proj.w": ("mamba", "out_proj", "kernel"),
}


def _path(name: str) -> tuple:
    if name == "embed_tokens":
        return ("embed", "embedding")
    if name == "norm.w":
        return ("final_norm", "scale")
    _, i, rest = name.split(".", 2)
    return (f"layer_{i}",) + _BLOCK[rest]


def to_program(params: dict, cfg: dict) -> dict:
    """The program's (flax) tree holding these values."""
    tree: dict = {}
    for name, value in params.items():
        node = tree
        *parents, last = _path(name)
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = value
    return tree


def from_program(tree: dict, cfg: dict) -> dict:
    """The same leaves under the reference's names."""
    out = {}
    for name in param_shapes(cfg, {}):
        node = tree
        for part in _path(name):
            node = node[part]
        out[name] = node
    return out


def leaves(tree: dict, cfg: dict) -> dict:
    """The leaves norms are taken over: ``in_proj`` split into its three
    tensors (the 64 columns of ``dt`` pass through softplus and the decay's
    exponential, the 4,096 of ``z`` through the gate: each answers for its own
    path) and the MLP's input matrix into its two halves."""
    s = _dims(cfg)
    out = {}
    for name, x in tree.items():
        if name.endswith("mamba.in_proj.w"):
            parts = jnp.split(x, [s["d_inner"], s["d_inner"] + s["conv_dim"]], axis=-1)
            out.update({name.replace("in_proj.", f"in_proj.{tag}."): part for tag, part in zip(("z", "xBC", "dt"), parts)})
        elif name.endswith("shared_mlp.input_linear.w"):
            parts = jnp.split(x, 2, axis=-1)
            out.update({name.replace("input_linear.", f"input_linear.{tag}."): part for tag, part in zip("ab", parts)})
        else:
            out[name] = x
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mm(a, w, rnd):
    return jnp.matmul(rnd(a), rnd(w), precision=HI)


def conv1d_causal(s, w, b):
    """``conv(s)_t = b + Σ_j w_j ⊙ s_{t-K+1+j}``, zeros before the sequence. ``s``: [B, T, C]; ``w``: [K, C]."""
    k, t = w.shape[0], s.shape[1]
    padded = jnp.pad(s, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[j] * padded[:, j:j + t] for j in range(k))


def ssd_sequential(x, dt, a, b, c):
    """``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ``, ``y_t = S_t C_t``, a step
    at a time. ``x``: [B, T, H, P]; ``dt``: [B, T, H]; ``a``: [H]; ``b``,
    ``c``: [B, T, N]. Returns ``y`` [B, T, H, P] (no ``D`` skip)."""
    rows, t, h, p = x.shape
    n = b.shape[-1]

    def step(state, at_t):
        x_t, dt_t, b_t, c_t = at_t
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)

    seg = next(s for s in range(min(SEGMENT, t), 0, -1) if t % s == 0)
    stretch = jax.checkpoint(lambda state, over: jax.lax.scan(step, state, over))
    by_time = [v.swapaxes(0, 1).reshape((t // seg, seg) + v.swapaxes(0, 1).shape[1:]) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(stretch, jnp.zeros((rows, h, p, n), jnp.float32), tuple(by_time))
    return y.reshape(t, rows, h, p).swapaxes(0, 1)


def _mamba(u, p, cfg, rnd):
    s = _dims(cfg)
    rows, t, _ = u.shape
    heads = cfg["mamba_n_heads"]
    z, xbc, dt = jnp.split(_mm(u, p["mamba.in_proj.w"], rnd), [s["d_inner"], s["d_inner"] + s["conv_dim"]], axis=-1)
    xbc = _silu(conv1d_causal(xbc, p["mamba.conv1d.w"], p["mamba.conv1d.b"]))
    x, b, c = jnp.split(xbc, [s["d_inner"], s["d_inner"] + s["n"]], axis=-1)
    x = x.reshape(rows, t, heads, cfg["mamba_d_head"])
    dt = jax.nn.softplus(dt + p["mamba.dt_bias"])
    # x, B and C are the operands of the scan's products (matmuls in the dual form): the control rounds them
    y = ssd_sequential(rnd(x), dt, -jnp.exp(p["mamba.A_log"]), rnd(b), rnd(c)) + p["mamba.D"][:, None] * x
    gated = _rms_norm(y.reshape(rows, t, s["d_inner"]) * _silu(z), p["mamba.norm.w"], cfg["rms_norm_eps"])
    return _mm(gated, p["mamba.out_proj.w"], rnd)


def _attention(x, p, cfg, rnd):
    s = _dims(cfg)
    rows, t, _ = x.shape
    heads, kv_heads, hd = s["heads"], s["kv_heads"], s["hd"]
    group = heads // kv_heads
    q = _mm(x, p["self_attn.q_proj.w"], rnd).reshape(rows, t, kv_heads, group, hd)
    k = _mm(x, p["self_attn.k_proj.w"], rnd).reshape(rows, t, kv_heads, hd)
    v = _mm(x, p["self_attn.v_proj.w"], rnd).reshape(rows, t, kv_heads, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_group(q_g, k_g, v_g):  # [B, T, group, hd], [B, T, hd], [B, T, hd]
        scores = cfg["attention_multiplier"] * jnp.einsum("bqgd,bkd->bgqk", rnd(q_g), rnd(k_g), precision=HI)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", rnd(probs), rnd(v_g), precision=HI)

    att = jnp.stack([one_group(q[:, :, g], k[:, :, g], v[:, :, g]) for g in range(kv_heads)], axis=2)
    return _mm(att.reshape(rows, t, heads * hd), p["self_attn.o_proj.w"], rnd)


def _layer(h, p, kind, cfg, rnd):
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = _attention if kind == "attention" else _mamba
    h = h + res * mixer(_rms_norm(h, p["input_layernorm.w"], eps), p, cfg, rnd)
    a, b = jnp.split(_mm(_rms_norm(h, p["post_attention_layernorm.w"], eps), p["shared_mlp.input_linear.w"], rnd), 2, axis=-1)
    return h + res * _mm(_silu(a) * b, p["shared_mlp.output_linear.w"], rnd)


def loss_sum(params: dict, batch: dict, cfg: dict, control=None):
    """Sum over the block's rows of the per-row mean next-token NLL (the
    caller divides by the step's rows)."""
    rnd = ROUNDERS[control]
    tokens, labels = batch["image"], batch["label"]
    with jax.default_matmul_precision("highest"):
        h = cfg["embedding_multiplier"] * params["embed_tokens"][tokens]
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layers.{i}."
            p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
            h = jax.checkpoint(lambda h, p, kind=kind: _layer(h, p, kind, cfg, rnd))(h, p)
        h = _rms_norm(h, params["norm.w"], cfg["rms_norm_eps"])
        logits = jnp.matmul(rnd(h), rnd(params["embed_tokens"]).T, precision=HI) / cfg["logits_scaling"]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.mean(nll, axis=-1))
