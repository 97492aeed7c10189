"""Required operations and bytes, from shapes alone.

"Required" means what the forward and backward passes of the mathematics
need: recomputed operations (rematerialised logits, the score matrix the
flash backward rebuilds) never count, and neither does the masked half of
causal attention. Corrected copies of ``bench.py``'s two functions (see
PERF.md section 3 for what was wrong with the originals).
"""

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


def flash_required_per_step(cfg: dict, seq_len: int, global_batch: int) -> dict:
    """FLOPs and HBM bytes the causal flash calls (forward + backward) of one
    optimizer step require. Bytes: the forward reads q, k, v and writes o;
    the backward reads q, k, v, o, do and writes dq, dk, dv -- twelve
    [B, T, d] tensors in the compute type (2 bytes), per layer. The
    log-sum-exp rows are a 1/head_dim-th of that and are left out."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    flops = lm_attention_flops_per_token(cfg, seq_len) * seq_len * global_batch
    bytes_ = 12.0 * 2.0 * layers * global_batch * seq_len * d
    return {"flops": flops, "bytes": bytes_}


def vgg16_forward_flops_per_image(cfg: dict, image_size: int, *, folded_fc1: bool = True) -> dict:
    """Forward multiply-adds x2 per image, itemised. ``folded_fc1``: after five
    2x2 pools a 32x32 image is a 1x1 map, the adaptive pool to 7x7 copies it
    49 times, so fc1's 25,088 inputs hold 512 distinct values; the product
    needs 512 x 4096 multiply-adds, not 25,088 x 4096. For larger images
    (map wider than 1x1) nothing folds."""
    size, cin = image_size, 3
    convs = []
    for feats, layers in zip(cfg["stage_features"], cfg["stage_layers"], strict=True):
        for _ in range(layers):
            convs.append(2.0 * 9.0 * cin * feats * size * size)
            cin = feats
        size //= 2
    fc_in = cin * 49
    if folded_fc1 and size == 1:
        fc_in = cin
    fcs = []
    for out in (*cfg["classifier_widths"], cfg["num_classes"]):
        fcs.append(2.0 * fc_in * out)
        fc_in = out
    return {"convs": convs, "fcs": fcs}


def vgg16_required_flops_per_step(
    cfg: dict, image_size: int, global_batch: int, *, folded_fc1: bool = True
) -> float:
    """Forward + backward (2x forward) per step; the first convolution's
    input gradient is not required (its input is data), so it counts 2x."""
    parts = vgg16_forward_flops_per_image(cfg, image_size, folded_fc1=folded_fc1)
    fwd = sum(parts["convs"]) + sum(parts["fcs"])
    return (3.0 * fwd - parts["convs"][0]) * global_batch


def required_flops_per_step(cfg: dict, traffic: dict) -> float:
    family = cfg["family"]
    if family == "lm":
        return lm_required_flops_per_step(cfg, traffic["seq_len"], traffic["global_batch"])
    if family == "vgg":
        return vgg16_required_flops_per_step(cfg, traffic["image_size"], traffic["global_batch"])
    raise ValueError(f"no required-FLOPs function for family {family!r}")
