"""Required operations and bytes of a GPT-2-shaped LM step (``n_embd``,
``n_layer``, ``n_inner``, tied head), from shapes alone. Corrected copy of
``bench.py``'s function (PERF.md section 3 says what was wrong with it)."""

from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matmul: the blocks and the tied head."""
    d, layers, inner = cfg["n_embd"], cfg["n_layer"], cfg["n_inner"]
    return layers * (4 * d * d + 2 * d * inner) + cfg["vocab_size"] * d


def lm_attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward + backward, per token: half of 12*L*T*d.

    Per sequence and layer the forward needs QK^T and PV over the unmasked
    half of the T x T square: 2 matmuls x 2*T*T*d / 2. The backward needs
    four (dV, dP, dQ, dK), twice the forward. 3 x 2*T*d per token."""
    return 6.0 * cfg["n_layer"] * seq_len * cfg["n_embd"]


def lm_required_flops_per_step(cfg: dict, seq_len: int, global_batch: int) -> float:
    per_token = 6.0 * lm_matmul_params(cfg) + lm_attention_flops_per_token(cfg, seq_len)
    return per_token * seq_len * global_batch


def lm_flash_required_per_step(cfg: dict, seq_len: int, global_batch: int) -> dict:
    """FLOPs and HBM bytes the causal flash calls (forward + backward) of one
    optimizer step require. Bytes: the forward reads q, k, v and writes o;
    the backward reads q, k, v, o, do and writes dq, dk, dv -- twelve
    [B, T, d] tensors in the compute type (2 bytes), per layer. The
    log-sum-exp rows are a 1/head_dim-th of that and are left out."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    flops = lm_attention_flops_per_token(cfg, seq_len) * seq_len * global_batch
    bytes_ = 12.0 * 2.0 * layers * global_batch * seq_len * d
    return {"flops": flops, "bytes": bytes_}


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    return lm_required_flops_per_step(cfg, traffic["seq_len"], traffic["global_batch"])


def flash_required_per_step(cfg: dict, traffic: dict) -> dict:
    return lm_flash_required_per_step(cfg, traffic["seq_len"], traffic["global_batch"])
