"""Itemize the analytic-vs-XLA FLOP gap on a bench step.

Compiles the exact ``bench.py`` executable and reconciles THREE counters:

* **model-analytic (nominal)** — the ``bench.py`` layer-formula count
  (2*M*N*K per layer, bwd = 2x fwd): the work an eager executor (the torch
  reference) performs for this model.
* **HLO-instruction sum (executed)** — every ``convolution``/``dot`` in the
  optimized module, counted with XLA's own convention
  (``utils.hlo_flops``): what the MXU actually runs after folding.
* **cost_analysis()** — XLA's total, which additionally counts VPU
  elementwise/reduce FLOPs.

and prints a per-instruction table with source-layer attribution
(HLO ``op_name`` metadata), grouping by pass (fwd / dgrad / wgrad).

Earlier finding (VGG16/32x32; a count from the compiled program, not a
speed): the fwd/dgrad/wgrad conv FLOPs reconcile per-instruction, and the
whole nominal-vs-executed gap is the degenerate classifier — at 32x32 the 1x1
feature map is replicated to 7x7 by the adaptive pool, and XLA folds the
replication out of the FC GEMMs (25088-wide -> effective 512-wide).

Scope: the HLO recount is trustworthy for conv-stack models (vgg16,
resnet50, convnext_l) where convolutions appear in canonical form. XLA:TPU
lowers transformer dot_generals to *windowed* convolutions whose taps are
mostly padding — there the kernel-spatial formula overcounts (measured 6.7x
on ViT-B) and ``utils.hlo_flops.executed_matmul_flops`` returns None via its
cost_analysis reconciliation guard.

Usage: BENCH_MODEL=vgg16 python scripts/itemize_flops.py
"""

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from distributed_training_pytorch_tpu.utils.hlo_flops import (
    itemize_hlo_matmul_flops,
    xla_cost_analysis,
)
from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng


def classify(row: dict) -> str:
    """Pass attribution from the op_name metadata (authoritative) with a
    dim_labels fallback."""
    op = row["op_name"]
    if "transpose(jvp" in op:
        # wgrad convs contract over the batch dim (batch rides a feature
        # label); dgrad convs keep the batch layout of the fwd.
        labels = row["dim_labels"]
        if row["kind"] == "dot":
            return "bwd-dot"
        lhs = labels.split("_")[0]
        return "wgrad" if not lhs.startswith("b") else "dgrad"
    if "jvp" in op or not op:
        return "fwd"
    return "other"


def main():
    enable_fast_rng()
    setup = bench.build_bench_setup(os.environ.get("BENCH_MODEL", "vgg16"))
    cfg, model = setup["cfg"], setup["model"]
    batch, image_size = setup["batch"], setup["image_size"]
    engine, state, gbatch = setup["engine"], setup["state"], setup["gbatch"]
    compiled = engine.compile_train_step(
        state, gbatch, compiler_options=setup["compiler_options"]
    )
    cost = xla_cost_analysis(compiled)
    xla_total = float(cost.get("flops", 0.0))
    model_total = cfg["flops"](model, image_size) * batch * cfg["items_per_row"](image_size)

    rows = itemize_hlo_matmul_flops(compiled.as_text())
    hlo_total = sum(r["flops"] for r in rows)

    print(f"# FLOP itemization: {setup['model_name']} batch={batch} size={image_size}")
    print(f"model-analytic (nominal) : {model_total:>18,.0f}  (bench.py 2MNK, bwd=2x fwd)")
    print(f"HLO conv/dot (executed)  : {hlo_total:>18,.0f}  ({len(rows)} instructions)")
    print(f"cost_analysis() flops    : {xla_total:>18,.0f}  (+VPU elementwise)")
    print(f"executed/nominal = {hlo_total/model_total:.4f}   "
          f"xla/nominal = {xla_total/model_total:.4f}")

    by_pass: dict[str, float] = defaultdict(float)
    for r in rows:
        by_pass[classify(r)] += r["flops"]
    print("\n## per-pass executed totals")
    for k, v in sorted(by_pass.items(), key=lambda kv: -kv[1]):
        print(f"  {k:8s} {v/1e9:>10.1f} GF")

    groups = defaultdict(lambda: [0, 0.0, ""])
    for r in rows:
        key = (r["kind"], classify(r), r["out_elems"], r["reduction"])
        groups[key][0] += 1
        groups[key][1] += r["flops"]
        # Shorten op_name to the layer path (after the model name).
        op = r["op_name"]
        groups[key][2] = op.split(")/")[-1][:60] or r["name"][:40]
    print("\n## instruction groups (by pass x output x reduction)")
    print(f"{'kind':5s} {'pass':6s} {'n':>3s} {'out_elems':>13s} {'reduction':>10s} "
          f"{'GFLOP':>9s}  source layer")
    for (kind, pss, oe, red), (cnt, fl, ex) in sorted(
        groups.items(), key=lambda kv: -kv[1][1]
    ):
        print(f"{kind:5s} {pss:6s} {cnt:>3d} {oe:>13,d} {red:>10,d} {fl/1e9:>9.1f}  {ex}")


if __name__ == "__main__":
    main()
