#!/usr/bin/env python
"""The fused tied cross-entropy head alone, at the LM cells' shapes (ISSUE 29).

Times ``jax.value_and_grad`` of ``ops.losses.tied_cross_entropy_loss`` (loss
and both gradients, bf16 operands as the policy casts them) for each slice
budget of a small grid and prints one JSON line per measurement; the table in
PERF.md §6 (PR 29) and ``ops.losses._SLICE_LOGITS_BYTES`` come from it. TPU only::

    chiprun --chips 1 -- python scripts/loss_head_sweep.py [--parent build/parent]

``--parent DIR`` also times the head of the checkout unpacked at DIR (the
parent commit: ``tied_cross_entropy`` + the weighted mean), for the before /
after columns. ``--compile-only`` compiles every point for a described v5e,
on one chip and under a ``data=4`` mesh, times nothing and counts what the
compiled text holds: matmuls (``convolution``) and all-reduces inside and
outside ``while`` bodies, temporaries, XLA's own FLOP count (runs without a
chip).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distributed_training_pytorch_tpu.analysis import hlo_audit
from distributed_training_pytorch_tpu.ops import losses

# (rows a chip, T, d): gpt2s_t1024 / gpt2s_t1024_dp4 and gpt2s_t4096
SHAPES = {"t1024_b32": (32, 1024, 768), "t4096_b8": (8, 4096, 768)}
TOKENS_A_SLICE = (2048, 4096, 8192)


def parent_head(root):
    """The head of the checkout at ``root`` as the parent's loss function has it."""
    path = os.path.join(root, "distributed_training_pytorch_tpu", "ops", "losses.py")
    spec = importlib.util.spec_from_file_location("parent_losses", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def head(hidden, embedding, targets, weights):
        return mod.weighted_mean(mod.tied_cross_entropy(hidden, embedding, targets).mean(-1), weights)

    return head


def body_counts(text):
    """Matmuls and all-reduces of a compiled module by whether a ``while`` runs them."""
    comps = hlo_audit.computations(text)
    inside = hlo_audit.called_from(comps, lambda ln: " while(" in ln)
    out = {}
    for op in ("convolution", "all-reduce"):
        hits = [n in inside for n, ls in comps.items() for ln in ls if re.search(rf" {op}(-start)?\(", ln)]
        out[op.replace("-", "_") + "s_in_loops"] = sum(hits)
        out[op.replace("-", "_") + "s_outside"] = len(hits) - sum(hits)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--vocab", default="50257", help="comma-separated vocabulary sizes")
    ap.add_argument("--out", default="chiprun_out/loss_head_sweep.jsonl")
    args = ap.parse_args()

    if args.compile_only:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        meshes = {1: None, 4: Mesh(topo.devices, ("data",))}
        one_chip = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        print(f"loss_head_sweep: needs a TPU, found {jax.default_backend()}", file=sys.stderr)
        return 2
    else:
        meshes = {1: None}
        one_chip = SingleDeviceSharding(jax.devices()[0])

    sides = [("change", tokens, losses.tied_cross_entropy_loss) for tokens in TOKENS_A_SLICE]
    if args.parent:
        sides.append(("parent", None, parent_head(args.parent)))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as sink:
        for name in args.shapes.split(","):
            rows, t, d = SHAPES[name]
            for vocab in map(int, args.vocab.split(",")):
                for (side, tokens, head), (chips, mesh) in ((s, m) for s in sides for m in meshes.items()):
                    row = {"shape": name, "vocab": vocab, "side": side, "chips": chips}
                    if tokens is not None:
                        # the sweep's one knob is the module's constant: the code has no argument for it
                        losses._SLICE_LOGITS_BYTES = 4 * tokens * vocab
                        row["tokens_a_slice"] = tokens
                    b = rows * chips
                    batch, whole = (one_chip,) * 2 if mesh is None else (
                        NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
                    specs = (
                        jax.ShapeDtypeStruct((b, t, d), jnp.bfloat16, sharding=batch),
                        jax.ShapeDtypeStruct((vocab, d), jnp.bfloat16, sharding=whole),
                        jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=batch),
                        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=batch),
                    )
                    fn = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))
                    try:
                        if mesh is None:
                            compiled = fn.lower(*specs).compile()
                        else:
                            with jax.sharding.set_mesh(mesh):  # the ambient mesh, as TrainEngine sets it
                                compiled = fn.lower(*specs).compile()
                        if args.compile_only:
                            row.update(body_counts(compiled.as_text()))
                            row["temp_gib"] = round(compiled.memory_analysis().temp_size_in_bytes / 2**30, 3)
                            row["xla_tflop"] = round(compiled.cost_analysis()["flops"] / 1e12, 3)
                        else:
                            key = jax.random.key(0)
                            operands = (
                                jax.random.normal(key, (b, t, d), jnp.bfloat16),
                                (jax.random.normal(key, (vocab, d)) * 0.02).astype(jnp.bfloat16),
                                jax.random.randint(key, (b, t), 0, vocab),
                                jnp.ones((b,), jnp.float32),
                            )
                            jax.block_until_ready(compiled(*operands))
                            t0 = time.perf_counter()
                            for _ in range(args.iters):
                                out = compiled(*operands)
                            jax.block_until_ready(out)
                            row["ms"] = round((time.perf_counter() - t0) / args.iters * 1e3, 3)
                            row["loss"] = float(out[0])
                            row["device_kind"] = jax.devices()[0].device_kind
                    except Exception as e:  # a refused point is a row of the table, not the end of it
                        row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    print(json.dumps(row), flush=True)
                    sink.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
