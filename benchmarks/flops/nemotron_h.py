"""Required operations and bytes of one step of the ``nemotron_h`` stack (a
layer is one mixer: Mamba-2 with grouped ``B`` / ``C``, grouped-query attention
or routed experts with a shared expert; untied head), from shapes alone.
Recomputation (the rematerialised blocks' second forward pass) never counts.
The whole step's count reckons the routed experts at an even routing: of a
token's ``num_experts_per_tok`` choices, ``held / published`` fall on this
chip (4.2% of the step's FLOPs at the published widths; a seed's routing gives
a fixed eight experts about half of that, so ``step_mfu`` reads about 2% of
itself high). ``moe_experts_required_per_step`` takes the pairs the routing
really gave, where its caller has them."""

from __future__ import annotations


def _kinds(cfg: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "M*E"}


def _published(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def local_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs a token sends to the experts held here, if routing is even."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / _published(cfg)


def parameter_count(cfg: dict) -> dict:
    """All parameters held, and those a token meets in a matmul: all but the
    norms, the convolution, ``dt_bias``, ``A_log``, ``D``, the router's bias
    and the embedding (a lookup), with one routed expert's two matrices
    counted ``local_pairs_per_token`` times and not ``held`` times."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    m_heads = cfg["mamba_num_heads"]
    d_inner = m_heads * cfg["mamba_head_dim"]
    conv_dim = d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba_mm = d * (d_inner + conv_dim + m_heads) + d_inner * d
    mamba_rest = conv_dim * cfg["conv_kernel"] + conv_dim + 3 * m_heads + d_inner
    attn_mm = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    expert = 2 * d * cfg["moe_intermediate_size"]
    shared = 2 * d * cfg["moe_shared_expert_intermediate_size"]
    router = _published(cfg) * d
    n = _kinds(cfg)
    embed = cfg["vocab_size"] * d
    matmul = (n["M"] * mamba_mm + n["*"] * attn_mm + n["E"] * (router + shared + local_pairs_per_token(cfg) * expert) + embed)
    held = (n["M"] * (mamba_mm + mamba_rest) + n["*"] * attn_mm
            + n["E"] * (router + _published(cfg) + shared + cfg["n_routed_experts"] * expert)
            + 2 * embed + (sum(n.values()) + 1) * d)
    return {"matmul": matmul, "all": held}


def scan_flops_per_token_layer(cfg: dict) -> float:
    """The sequential recurrence, forward, a token a layer: per head and
    element of its [head_dim, state] state the decay's multiply, the outer
    product's multiply and add, the read-out's multiply and add."""
    return 5.0 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward + backward, a token: ``flops/lm.py``'s count
    (6 T d a layer) at ``heads * head_dim`` wide (4096 here, not the hidden size)."""
    return 6.0 * _kinds(cfg)["*"] * seq_len * cfg["num_attention_heads"] * cfg["head_dim"]


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    t = traffic["seq_len"]
    per_token = (6.0 * parameter_count(cfg)["matmul"] + attention_flops_per_token(cfg, t)
                 + 3.0 * _kinds(cfg)["M"] * scan_flops_per_token_layer(cfg))
    return per_token * t * traffic["global_batch"]


def ssd_required_per_step(cfg: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the scan itself requires a step, whatever
    implements it, as ``flops/hybrid_lm.py`` reckons them with ``B`` and ``C``
    ``n_groups`` times as wide."""
    tokens = traffic["seq_len"] * traffic["global_batch"] * _kinds(cfg)["M"]
    heads, n = cfg["mamba_num_heads"], cfg["n_groups"] * cfg["ssm_state_size"]
    d_inner = heads * cfg["mamba_head_dim"]
    forward = 2.0 * (2 * d_inner + 2 * n) + 4.0 * heads
    backward = 2.0 * (3 * d_inner + 4 * n) + 4.0 * 2 * heads
    return {"flops": 3.0 * scan_flops_per_token_layer(cfg) * tokens, "bytes": (forward + backward) * tokens}


def moe_experts_required_per_step(cfg: dict, traffic: dict, pairs: float | None = None) -> dict:
    """FLOPs and HBM bytes the held experts' two products require a step,
    whatever implements them, for ``pairs`` (token, expert) pairs held here a
    step over all expert layers: what the step's routing gave (the program's
    ``moe.pairs_local`` counter), or where none is given an even routing's.
    FLOPs: a pair's row through ``[d, f]`` and ``[f, d]`` forward (2 · 2 d f)
    and twice that backward. Bytes: each held expert's two matrices read
    forward and read backward in the compute type (2) and their float32
    gradients written (4); a pair's row in and out forward, its gradient in
    and out backward, in the compute type (the ``f``-wide rows in between need
    not leave the chip). No implementation does less, recomputation counts
    against it, so a share of this roofline cannot pass 100%."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = _kinds(cfg)["E"]
    if pairs is None:
        pairs = traffic["seq_len"] * traffic["global_batch"] * local_pairs_per_token(cfg) * layers
    weights = layers * cfg["n_routed_experts"] * 2 * d * f
    return {"flops": 3.0 * 2 * 2 * d * f * pairs, "bytes": weights * (2.0 + 2.0 + 4.0) + pairs * 4 * d * 2.0}
