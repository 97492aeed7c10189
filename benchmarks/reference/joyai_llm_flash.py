"""The DeepSeek-V3 block as ``jdopensource/JoyAI-LLM-Flash`` publishes it, in
plain float32 ``jax.numpy``: every layer multi-head latent attention and then
a feed-forward, a dense SwiGLU MLP in the first ``first_k_dense_replace``
layers and routed SwiGLU experts with a shared expert after them; RMSNorm; an
untied head; one multi-token-prediction module whose loss joins the
next-token cross-entropy at a weight. Attention is a masked softmax over the
whole [T, T] square, ``HEADS_AT_ONCE`` heads at a time, with queries and keys
built whole (no kernel, no absorbed form); the rotary turns explicit pairs;
the expert layer scores every published expert, takes the top
``num_experts_per_tok`` and then walks the **held** experts one at a time over
every token with a mask of "this token chose e" (no sort, no buffer, no
grouped product); the logits are the full [T, V]. Recomputation only so that
a block of rows fits beside the weights: every layer is rematerialised, and
inside it each group of heads and each expert.

Equations, per token, ``n(x) = x / sqrt(mean(x²) + eps) * w``::

    h⁰ = E[id];  h ← h + MLA(n₁(h));  h ← h + FFN_l(n₂(h));  logits = n_f(h) W_headᵀ
    MLA: c_q = n_q(x W_qa);  [q_nope | q_rope] = c_q W_qb           (a head: 128 | 64)
         [c_kv | k_r] = x W_kva;  c_kv ← n_kv(c_kv);  [k_nope | v] = c_kv W_kvb   (128 | 128)
         q = [q_nope | R_p q_rope];  k = [k_nope | R_p k_r]  (k_r: one for all heads)
         R_p turns (x_2i, x_2i+1) by p · rope_theta^(−2i / qk_rope_head_dim)
         out = softmax(q kᵀ / sqrt(qk_head_dim), causal) v W_o
    dense FFN: (silu(x G) ⊙ x U) D
    expert FFN: s = sigmoid(x W_rᵀ);  top = the num_experts_per_tok largest of s + b_corr;
         w_e = routed_scaling_factor · s_e / (Σ_{e' ∈ top} s_{e'} + 1e-20);  f(x) = (silu(x G) ⊙ x U) D;
         out = Σ_{e ∈ top, e held} w_e f_e(x) + f_shared(x)
    MTP: h'_i = W_eh [n_h(h_i) ; n_e(E[t_{i+1}])]  (h: the last layer's output, t_{i+1} = label_i);
         h'' = an expert layer(h');  logits'_i = n_f'(h''_i) W_headᵀ predict t_{i+2} = label_{i+1}
    loss = mean_i CE(logits_i, label_i) + mtp_loss_weight · mean_{i ≤ T−2} CE(logits'_i, label_{i+1})

A pair routed to an expert that is not held (``n_routed_experts`` of the
``published`` count are, from ``experts_held_first`` on) adds nothing: the
partial sum of one expert-parallel rank, as the program computes it.

Names follow the published checkpoint's modules (``layers.{i}.self_attn.q_a_proj``,
``kv_a_proj_with_mqa``, ``mlp.gate``, ``mlp.experts``, ``mlp.shared_experts``;
the module's ``eh_proj``, ``enorm``, ``hnorm``); matrices are stored [in, out],
the router [experts, in] and the head [V, d] as published; a gate and its up
projection side by side as one [in, 2 · width] matrix (``gate_up_proj``), the
held experts' stacked [held, in, out], one leaf a layer and projection.
``to_program`` is the only place that knows the program's tree. Nothing here
imports the program.

Not in the published config, so assumed (the configuration file lists them):
the initialisation (N(0, ``initializer_range``) matrices, unit norms),
``b_corr`` zero and never updated, the module's loss weight (0.3) and the
order of its concatenation (hidden first), no auxiliary balance loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import HI, ROUNDERS

HEADS_AT_ONCE = 8  # heads whose [T, T] scores are built together: 8 x 256 MiB at T = 8192


def _dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"], "qr": cfg["q_lora_rank"], "kvr": cfg["kv_lora_rank"],
            "held": cfg["n_routed_experts"], "first": cfg.get("experts_held_first", 0),
            "published": cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])}


def _layer_shapes(cfg: dict, pre: str, dense: bool) -> dict:
    s = _dims(cfg)
    d, h = s["d"], s["heads"]
    shapes = {pre + "input_layernorm.w": (d,), pre + "post_attention_layernorm.w": (d,),
              pre + "self_attn.q_a_proj.w": (d, s["qr"]), pre + "self_attn.q_a_layernorm.w": (s["qr"],),
              pre + "self_attn.q_b_proj.w": (s["qr"], h * (s["nope"] + s["rope"])),
              pre + "self_attn.kv_a_proj_with_mqa.w": (d, s["kvr"] + s["rope"]), pre + "self_attn.kv_a_layernorm.w": (s["kvr"],),
              pre + "self_attn.kv_b_proj.w": (s["kvr"], h * (s["nope"] + s["v"])), pre + "self_attn.o_proj.w": (h * s["v"], d)}
    if dense:
        f = cfg["intermediate_size"]
        shapes.update({pre + "mlp.gate_up_proj.w": (d, 2 * f), pre + "mlp.down_proj.w": (f, d)})
    else:
        f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
        shapes.update({pre + "mlp.gate.w": (s["published"], d), pre + "mlp.gate.e_score_correction_bias": (s["published"],),
                       pre + "mlp.experts.gate_up_proj.w": (s["held"], d, 2 * f), pre + "mlp.experts.down_proj.w": (s["held"], f, d),
                       pre + "mlp.shared_experts.gate_up_proj.w": (d, 2 * fs), pre + "mlp.shared_experts.down_proj.w": (fs, d)})
    return shapes


def param_shapes(cfg: dict, traffic: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embeddings": (v, d), "norm_f.w": (d,), "lm_head.w": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update(_layer_shapes(cfg, f"layers.{i}.", i < cfg["first_k_dense_replace"]))
    if cfg["num_nextn_predict_layers"]:
        shapes.update({"mtp.hnorm.w": (d,), "mtp.enorm.w": (d,), "mtp.eh_proj.w": (2 * d, d), "mtp.norm.w": (d,)})
        shapes.update(_layer_shapes(cfg, "mtp.block.", False))
    return shapes


def init_params(cfg: dict, traffic: dict, key) -> dict:
    """The initialisation the configuration file lists under ``assumed``.
    One call, jit it: the weights are made on the device."""
    std = cfg["assumed"]["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg, traffic).items())):
        if name.endswith(("norm.w", "norm_f.w")):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("e_score_correction_bias"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return out


# reference name within a layer -> path in the program's block
_BLOCK = {
    "input_layernorm.w": ("input_layernorm", "scale"), "post_attention_layernorm.w": ("post_attention_layernorm", "scale"),
    "mlp.gate_up_proj.w": ("mlp_in", "kernel"), "mlp.down_proj.w": ("mlp_out", "kernel"),
    "mlp.gate.w": ("mlp", "router"), "mlp.gate.e_score_correction_bias": ("mlp", "score_correction_bias"),
    "mlp.experts.gate_up_proj.w": ("mlp", "experts_gate_up"), "mlp.experts.down_proj.w": ("mlp", "experts_down"),
    "mlp.shared_experts.gate_up_proj.w": ("mlp", "shared_gate_up", "kernel"),
    "mlp.shared_experts.down_proj.w": ("mlp", "shared_down", "kernel"),
}
for _proj in ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"):
    _BLOCK[f"self_attn.{_proj}.w"] = ("self_attn", _proj, "kernel")
for _norm in ("q_a_layernorm", "kv_a_layernorm"):
    _BLOCK[f"self_attn.{_norm}.w"] = ("self_attn", _norm, "scale")


def _path(name: str) -> tuple:
    top = {"embeddings": ("embed", "embedding"), "norm_f.w": ("final_norm", "scale"), "lm_head.w": ("lm_head",),
           "mtp.hnorm.w": ("mtp", "hnorm", "scale"), "mtp.enorm.w": ("mtp", "enorm", "scale"),
           "mtp.eh_proj.w": ("mtp", "eh_proj", "kernel"), "mtp.norm.w": ("mtp", "final_norm", "scale")}
    if name in top:
        return top[name]
    if name.startswith("mtp.block."):
        return ("mtp", "block") + _BLOCK[name[len("mtp.block."):]]
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
    """The leaves norms are taken over: each ``gate_up_proj`` split into its
    gate and its up projection, and ``kv_a_proj_with_mqa`` into the latent's
    columns and the rotary key's (a norm over the two together would hide one
    of them going wrong). The held experts' stacked matrices stay one leaf a
    layer and projection (``reference/nemotron_h.py:leaves`` says why)."""
    out = {}
    for name, x in tree.items():
        if name.endswith("gate_up_proj.w"):
            gate, up = jnp.split(x, 2, axis=-1)
            out[name.replace("gate_up_proj", "gate_proj")], out[name.replace("gate_up_proj", "up_proj")] = gate, up
        elif name.endswith("kv_a_proj_with_mqa.w"):
            latent, rope = jnp.split(x, [cfg["kv_lora_rank"]], axis=-1)
            out[name.replace(".w", ".latent.w")], out[name.replace(".w", ".rope.w")] = latent, rope
        else:
            out[name] = x
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mm(a, w, rnd):
    return jnp.matmul(rnd(a), rnd(w), precision=HI)


def rope_pairs(x, theta: float):
    """``x`` [B, T, ..., R]: pair ``(x_2i, x_2i+1)`` at position p turned by
    ``p · theta^(−2i / R)``, in place."""
    t, width = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]  # [T, R/2]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (width // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    turned_even = even * jnp.cos(angle) - odd * jnp.sin(angle)
    turned_odd = even * jnp.sin(angle) + odd * jnp.cos(angle)
    return jnp.stack([turned_even, turned_odd], axis=-1).reshape(x.shape)


def _mla(x, p, cfg, rnd):
    s = _dims(cfg)
    rows, t, _ = x.shape
    h, nope, rope, vd = s["heads"], s["nope"], s["rope"], s["v"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms_norm(_mm(x, p["self_attn.q_a_proj.w"], rnd), p["self_attn.q_a_layernorm.w"], eps)
    q = _mm(c_q, p["self_attn.q_b_proj.w"], rnd).reshape(rows, t, h, nope + rope)
    kv_a = _mm(x, p["self_attn.kv_a_proj_with_mqa.w"], rnd)
    c_kv = _rms_norm(kv_a[..., :s["kvr"]], p["self_attn.kv_a_layernorm.w"], eps)
    kv = _mm(c_kv, p["self_attn.kv_b_proj.w"], rnd).reshape(rows, t, h, nope + vd)
    k_rope = rope_pairs(kv_a[..., s["kvr"]:], cfg["rope_theta"])  # [B, T, R], one for every head
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], cfg["rope_theta"])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :], (rows, t, h, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(_, group):  # [B, T, g, nope + rope], the same, [B, T, g, v]
        q_g, k_g, v_g = group
        scores = (nope + rope) ** -0.5 * jnp.einsum("bqhd,bkhd->bhqk", rnd(q_g), rnd(k_g), precision=HI)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v_g), precision=HI)

    g = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else h
    by_group = lambda x: jnp.moveaxis(x.reshape(rows, t, h // g, g, x.shape[-1]), 2, 0)  # noqa: E731
    # one group's body, compiled once and walked in turn (an unrolled loop compiled each copy: minutes at the cell's size)
    _, att = jax.lax.scan(jax.checkpoint(some_heads), None, (by_group(q), by_group(k), by_group(v)))
    return _mm(jnp.moveaxis(att, 0, 2).reshape(rows, t, h * vd), p["self_attn.o_proj.w"], rnd)


def _swiglu(x, gate_up, down, rnd):
    gate, up = jnp.split(_mm(x, gate_up, rnd), 2, axis=-1)
    return _mm(_silu(gate) * up, down, rnd)


def routing(x, p, cfg):
    """``(top, weights)``: each token's chosen experts ``[..., k]`` over the
    published count and the weights it gives them. The router is float32 in
    every precision the configuration or its control states."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["mlp.gate.w"].T, precision=HI))
    _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(p["mlp.gate.e_score_correction_bias"]), cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, top, axis=-1)
    return top, cfg["routed_scaling_factor"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _moe(x, p, cfg, rnd):
    s = _dims(cfg)
    top, weights = routing(x, p, cfg)

    def one_expert(out, expert):  # every token through the expert; its weight is 0 where it was not chosen
        e, gate_up, down = expert
        chose_e = jnp.sum(jnp.where(top == s["first"] + e, weights, 0.0), axis=-1)
        return out + chose_e[..., None] * _swiglu(x, gate_up, down, rnd), None

    out = _swiglu(x, p["mlp.shared_experts.gate_up_proj.w"], p["mlp.shared_experts.down_proj.w"], rnd)
    experts = (jnp.arange(s["held"]), p["mlp.experts.gate_up_proj.w"], p["mlp.experts.down_proj.w"])
    return jax.lax.scan(jax.checkpoint(one_expert), out, experts)[0]


def _layer(h, p, cfg, rnd):
    eps = cfg["rms_norm_eps"]
    h = h + _mla(_rms_norm(h, p["input_layernorm.w"], eps), p, cfg, rnd)
    x = _rms_norm(h, p["post_attention_layernorm.w"], eps)
    if "mlp.gate_up_proj.w" in p:
        return h + _swiglu(x, p["mlp.gate_up_proj.w"], p["mlp.down_proj.w"], rnd)
    return h + _moe(x, p, cfg, rnd)


def _sub(params: dict, pre: str) -> dict:
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(params: dict, tokens, cfg: dict, control=None, labels=None):
    """The final norm's output ``[B, T, d]`` and, given ``labels`` and a
    prediction module, the module's ``[B, T, d]`` (else None)."""
    rnd = ROUNDERS[control]
    eps = cfg["rms_norm_eps"]
    h = params["embeddings"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(lambda h, p: _layer(h, p, cfg, rnd))(h, _sub(params, f"layers.{i}."))
    mtp = None
    if labels is not None and cfg["num_nextn_predict_layers"]:
        joined = jnp.concatenate([_rms_norm(h, params["mtp.hnorm.w"], eps),
                                  _rms_norm(params["embeddings"][labels], params["mtp.enorm.w"], eps)], axis=-1)
        x = _mm(joined, params["mtp.eh_proj.w"], rnd)
        x = jax.checkpoint(lambda x, p: _layer(x, p, cfg, rnd))(x, _sub(params, "mtp.block."))
        mtp = _rms_norm(x, params["mtp.norm.w"], eps)
    return _rms_norm(h, params["norm_f.w"], eps), mtp


def _nll(h, head, labels, rnd):
    logits = jnp.matmul(rnd(h), rnd(head).T, precision=HI)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]


def loss_sum(params: dict, batch: dict, cfg: dict, control=None):
    """Sum over the block's rows of each row's objective: the mean next-token
    NLL plus ``mtp_loss_weight`` times the module's mean NLL of the token after
    the next over positions 0 … T − 2 (the caller divides by the step's rows)."""
    rnd = ROUNDERS[control]
    tokens, labels = batch["image"], batch["label"]
    with jax.default_matmul_precision("highest"):
        h, mtp = hidden_states(params, tokens, cfg, control, labels)
        per_row = jnp.mean(_nll(h, params["lm_head.w"], labels, rnd), axis=-1)
        if mtp is not None:
            ahead = _nll(mtp[:, :-1], params["lm_head.w"], labels[:, 1:], rnd)
            per_row = per_row + cfg["assumed"]["mtp_loss_weight"] * jnp.mean(ahead, axis=-1)
        return jnp.sum(per_row)
