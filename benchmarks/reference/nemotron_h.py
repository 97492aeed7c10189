"""The ``nemotron_h`` stack (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B``) in plain
float32 ``jax.numpy``: a layer is one mixer, ``h ← h + mixer(n(h))``, by the
characters of ``hybrid_override_pattern`` a Mamba-2 mixer (``M``), routed
experts (``E``) or grouped-query attention (``*``); RMSNorm, no positions, an
untied head, mean next-token cross-entropy and nothing else in the objective.
The state-space layer is the **sequential recurrence over time** (a
``lax.scan`` of T steps) with ``B`` and ``C`` a group, not the chunked dual form
the program runs; attention is a masked softmax over the whole [T, T] square, a
key/value head at a time with its sixteen query heads; the expert layer scores
every published expert, takes the top ``num_experts_per_tok`` and then walks
the **held** experts one at a time over every token with a mask of "this token
chose e" (no sort, no buffer, no grouped product); the logits are the full
[T, V]. No kernel, no cache. Recomputation only so that a block of rows fits
beside the weights: every layer is rematerialised, and inside it each stretch
of ``SEGMENT`` steps of the time scan, each key/value group and each expert.

Equations, per token, ``n(x) = x / sqrt(mean(x²) + eps) * w``::

    h⁰ = E[id];  h ← h + mixer_l(n_l(h));  logits = n_f(h) W_headᵀ
    M:  [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC)), conv(s)_t = b + Σ_j w_j ⊙ s_{t-K+1+j};
        [x | B | C] = xBC (B, C: n_groups × ssm_state_size; head h reads group h // (heads / n_groups));
        Δ = softplus(dt + dt_bias);  A = −exp(A_log);
        S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ (S_0 = 0);  y_t = S_t C_t + D x_t;
        g = y ⊙ silu(z);  out = (g / sqrt(mean over each group's channels of g² + eps) ⊙ w) W_out
    *:  softmax(q kᵀ / sqrt(head_dim), causal) v; key/value head j serves query heads j·r … j·r + r − 1
    E:  s = sigmoid(x W_rᵀ);  top = the num_experts_per_tok largest of s + b_corr;
        w_e = routed_scaling_factor · s_e / (Σ_{e' ∈ top} s_{e'} + 1e-20);  f(x) = relu(x U)² V;
        out = Σ_{e ∈ top, e held} w_e f_e(x) + f_shared(x)

A pair routed to an expert that is not held (``n_routed_experts`` of the
``published`` count are, from ``experts_held_first`` on) adds nothing: the
partial sum of one expert-parallel rank, as the program computes it.

Names follow the published checkpoint's modules (``layers.{i}.mixer.in_proj``,
``mixer.gate``, ``mixer.experts``, ``mixer.shared_experts``, ``norm_f``,
``lm_head``); matrices are stored [in, out], the router [experts, in] and the
head [V, d] as published; the held experts' matrices are stacked [held, in,
out], one leaf a layer and projection (:func:`leaves` says why not one an expert).
``to_program`` is the only place that knows the program's tree. Nothing here
imports the program.

Not in the published config, so assumed (the configuration file lists them):
the initialisation (as ``granite_hybrid.py``'s: N(0, ``initializer_range``)
matrices, the router's among them, unit norms, Mamba-2's own for what is the
mixer's alone), ``b_corr`` zero and never updated by a balancing rule (its rate
is in no key; it takes part in AdamW as a leaf whose gradient is zero), no
auxiliary loss, the order ``[z | xBC | dt]`` of ``in_proj``'s columns, and no
positions in attention.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import HI, ROUNDERS

SEGMENT = 64  # steps of the time scan rematerialised together: a step saves one [heads, 64, 128] state


def _dims(cfg: dict) -> dict:
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return {"d": cfg["hidden_size"], "d_inner": d_inner, "n": cfg["ssm_state_size"], "groups": cfg["n_groups"],
            "conv_dim": d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
            "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "held": cfg["n_routed_experts"], "first": cfg.get("experts_held_first", 0),
            "published": cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])}


def param_shapes(cfg: dict, traffic: dict) -> dict:
    s = _dims(cfg)
    d = s["d"]
    shapes = {"embeddings": (cfg["vocab_size"], d), "norm_f.w": (d,), "lm_head.w": (cfg["vocab_size"], d)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"layers.{i}."
        shapes[pre + "norm.w"] = (d,)
        if kind == "*":
            shapes.update({pre + "mixer.q_proj.w": (d, s["heads"] * s["hd"]), pre + "mixer.k_proj.w": (d, s["kv_heads"] * s["hd"]),
                           pre + "mixer.v_proj.w": (d, s["kv_heads"] * s["hd"]), pre + "mixer.o_proj.w": (s["heads"] * s["hd"], d)})
        elif kind == "M":
            h = cfg["mamba_num_heads"]
            shapes.update({pre + "mixer.in_proj.w": (d, s["d_inner"] + s["conv_dim"] + h),
                           pre + "mixer.conv1d.w": (cfg["conv_kernel"], s["conv_dim"]), pre + "mixer.conv1d.b": (s["conv_dim"],),
                           pre + "mixer.dt_bias": (h,), pre + "mixer.A_log": (h,), pre + "mixer.D": (h,),
                           pre + "mixer.norm.w": (s["d_inner"],), pre + "mixer.out_proj.w": (s["d_inner"], d)})
        elif kind == "E":
            f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
            shapes.update({pre + "mixer.gate.w": (s["published"], d), pre + "mixer.gate.e_score_correction_bias": (s["published"],),
                           pre + "mixer.experts.up_proj.w": (s["held"], d, f), pre + "mixer.experts.down_proj.w": (s["held"], f, d),
                           pre + "mixer.shared_experts.up_proj.w": (d, fs), pre + "mixer.shared_experts.down_proj.w": (fs, d)})
        else:
            raise ValueError(f"layer kind {kind!r} of hybrid_override_pattern is not in this reference (want M, E or *)")
    return shapes


def init_params(cfg: dict, traffic: dict, key) -> dict:
    """The initialisation the configuration file lists under ``assumed``.
    One call, jit it: the weights are made on the device."""
    std = cfg["assumed"]["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg, traffic).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith(("norm.w", "norm_f.w", ".D")):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("conv1d.b", "e_score_correction_bias")):
            out[name] = jnp.zeros(shape, jnp.float32)
        elif name.endswith("conv1d.w"):
            bound = cfg["conv_kernel"] ** -0.5
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
    "norm.w": ("input_layernorm", "scale"),
    "mixer.q_proj.w": ("self_attn", "q_proj", "kernel"), "mixer.k_proj.w": ("self_attn", "k_proj", "kernel"),
    "mixer.v_proj.w": ("self_attn", "v_proj", "kernel"), "mixer.o_proj.w": ("self_attn", "o_proj", "kernel"),
    "mixer.in_proj.w": ("mamba", "in_proj", "kernel"), "mixer.conv1d.w": ("mamba", "conv_kernel"),
    "mixer.conv1d.b": ("mamba", "conv_bias"), "mixer.dt_bias": ("mamba", "dt_bias"), "mixer.A_log": ("mamba", "A_log"),
    "mixer.D": ("mamba", "D"), "mixer.norm.w": ("mamba", "norm", "scale"), "mixer.out_proj.w": ("mamba", "out_proj", "kernel"),
    "mixer.gate.w": ("moe", "router"), "mixer.gate.e_score_correction_bias": ("moe", "score_correction_bias"),
    "mixer.experts.up_proj.w": ("moe", "experts_up"), "mixer.experts.down_proj.w": ("moe", "experts_down"),
    "mixer.shared_experts.up_proj.w": ("moe", "shared_up", "kernel"),
    "mixer.shared_experts.down_proj.w": ("moe", "shared_down", "kernel"),
}


def _path(name: str) -> tuple:
    top = {"embeddings": ("embed", "embedding"), "norm_f.w": ("final_norm", "scale"), "lm_head.w": ("lm_head",)}
    if name in top:
        return top[name]
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
    tensors (as ``granite_hybrid.py`` has it, and why). The held experts'
    stacked matrices stay one leaf a layer and projection: a leaf an expert
    cannot carry a limit at the cell's size (PERF.md section 6, PR 36: the
    bfloat16 program and the float32 reference send a few of an expert's
    ~400-800 tokens elsewhere, a near-tie under rounding, and over 35 seeds the
    worst single expert's norm read up to 2.5% off where the fp8 control's
    least reading is 2.6%; over the eight together the program reads at most
    0.75% over 40 seeds and the control at least 1.0% on 15 of 17, 0.82% and
    0.90% on the other two: no grouping tells the two apart on every seed,
    and PERF.md section 7 says what would). That an expert holds its own rows
    and no other's is held element by element on the CPU instead
    (``tests/test_nemotron_h.py``, ``tests/test_moe.py``)."""
    s = _dims(cfg)
    out = {}
    for name, x in tree.items():
        if name.endswith("mixer.in_proj.w"):
            parts = jnp.split(x, [s["d_inner"], s["d_inner"] + s["conv_dim"]], axis=-1)
            out.update({name.replace("in_proj.", f"in_proj.{tag}."): part for tag, part in zip(("z", "xBC", "dt"), parts)})
        else:
            out[name] = x
    return out


def _rms_norm(x, w, eps, groups: int = 1):
    runs = x.reshape(x.shape[:-1] + (groups, -1))
    return (runs * jax.lax.rsqrt(jnp.mean(jnp.square(runs), axis=-1, keepdims=True) + eps)).reshape(x.shape) * w


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
    ``c``: [B, T, G, N], head ``h`` reading group ``h // (H / G)``. Returns
    ``y`` [B, T, H, P] (no ``D`` skip)."""
    rows, t, h, p = x.shape
    g, n = b.shape[2:]

    def step(state, at_t):
        x_t, dt_t, b_t, c_t = at_t
        b_t, c_t = (jnp.repeat(v, h // g, axis=1)[:, :, None, :] for v in (b_t, c_t))  # a head's own: [B, H, 1, N]
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t
        return state, jnp.sum(state * c_t, axis=-1)

    seg = next(s for s in range(min(SEGMENT, t), 0, -1) if t % s == 0)
    stretch = jax.checkpoint(lambda state, over: jax.lax.scan(step, state, over))
    by_time = [v.swapaxes(0, 1).reshape((t // seg, seg) + v.swapaxes(0, 1).shape[1:]) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(stretch, jnp.zeros((rows, h, p, n), jnp.float32), tuple(by_time))
    return y.reshape(t, rows, h, p).swapaxes(0, 1)


def _mamba(u, p, cfg, rnd):
    s = _dims(cfg)
    rows, t, _ = u.shape
    heads, groups, n = cfg["mamba_num_heads"], s["groups"], s["n"]
    z, xbc, dt = jnp.split(_mm(u, p["mixer.in_proj.w"], rnd), [s["d_inner"], s["d_inner"] + s["conv_dim"]], axis=-1)
    xbc = _silu(conv1d_causal(xbc, p["mixer.conv1d.w"], p["mixer.conv1d.b"]))
    x, b, c = jnp.split(xbc, [s["d_inner"], s["d_inner"] + groups * n], axis=-1)
    x = x.reshape(rows, t, heads, cfg["mamba_head_dim"])
    b, c = b.reshape(rows, t, groups, n), c.reshape(rows, t, groups, n)
    dt = jax.nn.softplus(dt + p["mixer.dt_bias"])
    # x, B and C are the operands of the scan's products (matmuls in the dual form): the control rounds them
    y = ssd_sequential(rnd(x), dt, -jnp.exp(p["mixer.A_log"]), rnd(b), rnd(c)) + p["mixer.D"][:, None] * x
    gated = _rms_norm(y.reshape(rows, t, s["d_inner"]) * _silu(z), p["mixer.norm.w"], cfg["layer_norm_epsilon"], groups)
    return _mm(gated, p["mixer.out_proj.w"], rnd)


def _attention(x, p, cfg, rnd):
    s = _dims(cfg)
    rows, t, _ = x.shape
    heads, kv_heads, hd = s["heads"], s["kv_heads"], s["hd"]
    group = heads // kv_heads
    q = _mm(x, p["mixer.q_proj.w"], rnd).reshape(rows, t, kv_heads, group, hd)
    k = _mm(x, p["mixer.k_proj.w"], rnd).reshape(rows, t, kv_heads, hd)
    v = _mm(x, p["mixer.v_proj.w"], rnd).reshape(rows, t, kv_heads, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_group(q_g, k_g, v_g):  # [B, T, group, hd], [B, T, hd], [B, T, hd]
        scores = hd**-0.5 * jnp.einsum("bqgd,bkd->bgqk", rnd(q_g), rnd(k_g), precision=HI)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", rnd(probs), rnd(v_g), precision=HI)

    att = jnp.stack([one_group(q[:, :, g], k[:, :, g], v[:, :, g]) for g in range(kv_heads)], axis=2)
    return _mm(att.reshape(rows, t, heads * hd), p["mixer.o_proj.w"], rnd)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def routing(x, p, cfg):
    """``(top, weights)``: each token's chosen experts ``[..., k]`` over the
    published count and the weights it gives them. The router is float32 in
    every precision the configuration or its control states."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["mixer.gate.w"].T, precision=HI))
    _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(p["mixer.gate.e_score_correction_bias"]), cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, top, axis=-1)
    return top, cfg["routed_scaling_factor"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _moe(x, p, cfg, rnd):
    s = _dims(cfg)
    top, weights = routing(x, p, cfg)

    @jax.checkpoint
    def one_expert(x, up, down, weight):  # every token through the expert; `weight` is 0 where it was not chosen
        return weight[..., None] * _mm(_relu2(_mm(x, up, rnd)), down, rnd)

    out = _mm(_relu2(_mm(x, p["mixer.shared_experts.up_proj.w"], rnd)), p["mixer.shared_experts.down_proj.w"], rnd)
    for e in range(s["held"]):
        chose_e = jnp.sum(jnp.where(top == s["first"] + e, weights, 0.0), axis=-1)
        out = out + one_expert(x, p["mixer.experts.up_proj.w"][e], p["mixer.experts.down_proj.w"][e], chose_e)
    return out


_MIXERS = {"M": _mamba, "*": _attention, "E": _moe}


def _layer(h, p, kind, cfg, rnd):
    return h + _MIXERS[kind](_rms_norm(h, p["norm.w"], cfg["layer_norm_epsilon"]), p, cfg, rnd)


def hidden_states(params: dict, tokens, cfg: dict, control=None):
    """The final norm's output ``[B, T, d]``."""
    rnd = ROUNDERS[control]
    h = params["embeddings"][tokens]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"layers.{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h = jax.checkpoint(lambda h, p, kind=kind: _layer(h, p, kind, cfg, rnd))(h, p)
    return _rms_norm(h, params["norm_f.w"], cfg["layer_norm_epsilon"])


def loss_sum(params: dict, batch: dict, cfg: dict, control=None):
    """Sum over the block's rows of the per-row mean next-token NLL (the
    caller divides by the step's rows)."""
    rnd = ROUNDERS[control]
    tokens, labels = batch["image"], batch["label"]
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, cfg, control)
        logits = jnp.matmul(rnd(h), rnd(params["lm_head.w"]).T, precision=HI)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.mean(nll, axis=-1))
