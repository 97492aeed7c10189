"""Required operations of a VGG step (``stage_features``, ``stage_layers``,
``classifier_widths``), from shapes alone. Corrected copy of ``bench.py``'s
function (PERF.md section 3 says what was wrong with it)."""

from __future__ import annotations


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
    return vgg16_required_flops_per_step(cfg, traffic["image_size"], traffic["global_batch"])
