"""Required operations and bytes of one step of the hybrid state-space /
attention LM (``hidden_size``, ``layer_types``, ``mamba_*``, a shared gated
MLP, tied head), from shapes alone. Recomputation (the rematerialised blocks'
second forward pass) never counts."""

from __future__ import annotations


def _kinds(cfg: dict) -> tuple[int, int]:
    attention = sum(kind == "attention" for kind in cfg["layer_types"])
    return len(cfg["layer_types"]) - attention, attention


def parameter_count(cfg: dict) -> dict:
    """All parameters, and those that sit in a matmul (all but the norms, the
    convolution, ``dt_bias``, ``A_log`` and ``D``; the tied embedding once, as
    the head)."""
    d, inner = cfg["hidden_size"], cfg["shared_intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    m_heads = cfg["mamba_n_heads"]
    d_inner = m_heads * cfg["mamba_d_head"]
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    mlp = d * 2 * inner + inner * d
    mamba_mm = d * (d_inner + conv_dim + m_heads) + d_inner * d
    mamba_rest = conv_dim * cfg["mamba_d_conv"] + conv_dim + 3 * m_heads + d_inner
    attn_mm = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    n_mamba, n_attn = _kinds(cfg)
    embed = cfg["vocab_size"] * d
    matmul = n_mamba * (mamba_mm + mlp) + n_attn * (attn_mm + mlp) + embed
    return {"matmul": matmul, "all": matmul + n_mamba * mamba_rest + (n_mamba + n_attn) * 2 * d + d}


def scan_flops_per_token_layer(cfg: dict) -> float:
    """The sequential recurrence, forward, a token a layer: per head and
    element of its [d_head, d_state] state the decay's multiply, the outer
    product's multiply and add, the read-out's multiply and add."""
    return 5.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward + backward, a token: ``flops/lm.py``'s count
    (6 T d a layer) for the attention layers at ``heads * head_dim`` wide."""
    _, n_attn = _kinds(cfg)
    return 6.0 * n_attn * seq_len * cfg["hidden_size"]


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    n_mamba, _ = _kinds(cfg)
    t = traffic["seq_len"]
    per_token = (6.0 * parameter_count(cfg)["matmul"] + attention_flops_per_token(cfg, t)
                 + 3.0 * n_mamba * scan_flops_per_token_layer(cfg))
    return per_token * t * traffic["global_batch"]


def ssd_required_per_step(cfg: dict, traffic: dict) -> dict:
    """FLOPs and HBM bytes the scan itself requires a step, whatever
    implements it. FLOPs: the sequential recurrence's, forward and twice that
    backward. Bytes, in the compute type (2): forward reads ``x``
    [heads * d_head], ``B``, ``C`` [d_state each] and writes ``y``; backward
    reads those inputs and ``dy`` and writes the gradients of ``x``, ``B``,
    ``C``; ``Δ`` [heads] is float32 (4) wherever it goes: read forward, read
    and its gradient written backward. No implementation moves fewer bytes
    than its inputs and outputs, so a share of this roofline cannot pass 100%."""
    n_mamba, _ = _kinds(cfg)
    tokens = traffic["seq_len"] * traffic["global_batch"] * n_mamba
    heads, n = cfg["mamba_n_heads"], cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    d_inner = heads * cfg["mamba_d_head"]
    forward = 2.0 * (2 * d_inner + 2 * n) + 4.0 * heads
    backward = 2.0 * (3 * d_inner + 4 * n) + 4.0 * 2 * heads
    return {"flops": 3.0 * scan_flops_per_token_layer(cfg) * tokens, "bytes": (forward + backward) * tokens}
