"""Required operations and bytes of one step of the DeepSeek-V3 block as
``JoyAI-LLM-Flash`` publishes it (latent attention on every layer, a dense
SwiGLU MLP in the first ``first_k_dense_replace`` layers and routed SwiGLU
experts with a shared expert after them, one multi-token-prediction module,
untied head), from shapes alone. Recomputation (the rematerialised blocks'
second forward pass) never counts, and neither does the masked half of causal
attention. The routed experts count at an even routing: of a token's
``num_experts_per_tok`` choices, ``held / published`` fall on this chip. The
head is counted twice, once for the main prediction and once for the module's.
"""

from __future__ import annotations


def _published(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def local_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs a token sends to the experts held here, if routing is even."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / _published(cfg)


def mla_matmul_params(cfg: dict) -> int:
    """A latent-attention layer's five projections: q_a, q_b, kv_a (latent and
    rotary key), kv_b, o."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return d * qr + qr * heads * (nope + rope) + d * (kvr + rope) + kvr * heads * (nope + vd) + heads * vd * d


def parameter_count(cfg: dict) -> dict:
    """All parameters held, and those a token meets in a matmul: all but the
    norms, the router's bias and the embedding (a lookup), one routed
    expert's three matrices counted ``local_pairs_per_token`` times and not
    ``held`` times, and the head twice (the prediction module passes through
    it too)."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mla = mla_matmul_params(cfg)
    mla_norms = cfg["q_lora_rank"] + cfg["kv_lora_rank"] + 2 * d  # q_a, kv_a, and the block's two
    dense_mlp = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    router = _published(cfg) * d
    moe_matmul = router + shared + local_pairs_per_token(cfg) * expert
    moe_held = router + _published(cfg) + shared + cfg["n_routed_experts"] * expert
    mtp = cfg["num_nextn_predict_layers"]
    head = cfg["vocab_size"] * d
    matmul = (layers * mla + dense * dense_mlp + (layers - dense) * moe_matmul
              + mtp * (2 * d * d + mla + moe_matmul) + (1 + mtp) * head)
    held = (layers * (mla + mla_norms) + dense * dense_mlp + (layers - dense) * moe_held
            + mtp * (2 * d * d + 3 * d + mla + mla_norms + moe_held) + 2 * head + d)
    return {"matmul": matmul, "all": held}


def attention_layers(cfg: dict) -> int:
    """Latent-attention layers a token passes: the stack's and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward + backward, a token: over the unmasked half of
    the square, ``q kᵀ`` at ``nope + rope`` wide and ``p v`` at ``v_head_dim``,
    a head: ``T (Dqk + Dv)`` forward, twice that backward."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 3.0 * attention_layers(cfg) * seq_len * cfg["num_attention_heads"] * (dqk + cfg["v_head_dim"])


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    t = traffic["seq_len"]
    per_token = 6.0 * parameter_count(cfg)["matmul"] + attention_flops_per_token(cfg, t)
    return per_token * t * traffic["global_batch"]


def flash_required_per_step(cfg: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the causal flash calls of the latent-attention
    layers (forward + backward) require a step, whatever implements them
    (read by `flash_roofline` and `mla_flash_roofline`).
    Bytes, in the compute type (2), a head of a token: the forward reads q, k
    (``Dqk`` each) and v and writes o (``Dv`` each); the backward reads q, k,
    v, o, dO and writes dq, dk, dv: ``6 Dqk + 6 Dv`` in all. The log-sum-exp
    rows are a ``1 / Dv``-th of that and are left out."""
    t, rows = traffic["seq_len"], traffic["global_batch"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_token_layer = cfg["num_attention_heads"] * (6 * dqk + 6 * cfg["v_head_dim"]) * 2.0
    return {"flops": attention_flops_per_token(cfg, t) * t * rows,
            "bytes": per_token_layer * attention_layers(cfg) * t * rows}
