#!/usr/bin/env python
"""Block-shape sweep of the flash kernels, each alone (ISSUE 26, ISSUE 30, ISSUE 32).

Times ``flash_fwd`` and the fused backward ``flash_dqkv`` (``ops/pallas.py``)
at the LM cells' shapes for every ``(block_q, block_k)`` of a small grid and
prints one JSON line per measurement; the tables in PERF.md §6 (PR 26, PR 30,
PR 32) and the rules in ``ops.pallas._flash_blocks`` / ``_flash_sub`` come from
it. TPU only::

    chiprun --chips 1 -- python scripts/flash_block_sweep.py [--parent build/parent]

``--sub 512,256`` sweeps the other axis instead: a causal call of one block
pair (T <= 1024) as one tile and walked in static sub-tiles of each size
given, each kernel alone (``ops.pallas._flash_sub``'s table).

``--parent DIR`` also times the kernels of the checkout unpacked at DIR (the
parent commit), each at the block shape that checkout's own rule gives it, for
the before / after columns: a checkout from before ISSUE 30 has two backward
kernels, ``dq`` and ``dkv``, whose sum is what ``bwd`` replaced.
``--compile-only`` compiles every point for a described v5e and times nothing
(runs without a chip: what Mosaic refuses there it refuses on the chip).

What "alone" includes: XLA gives the entry parameters of such a one-kernel
program a layout of its own (T minor-most for ``[B, H, T, 64]``), so every
call also transposes each operand and result into and out of the kernel's
row-major layout — 0.8 ms a forward call and 1.5 ms a backward call of
``[32, 12, 1024, 64]``, the same for every block shape of a row, which is why
the tables rank shapes rightly and overstate a call's time (in the step the
producers write the kernel's layout and a call takes 1.17 / 2.70 ms where the
tables say 1.98 / 4.21). ``--kernel-layout`` pins operands and results to the
kernel's layout and times the kernel by itself.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

SHAPES = {"t4096_b8": (8, 12, 4096, 64), "t1024_b32": (32, 12, 1024, 64), "t8192_b4": (4, 12, 8192, 64)}
CELL_SHAPES = "t4096_b8,t1024_b32"
BLOCKS = (256, 512, 1024)


def load_pallas(root):
    """``ops/pallas.py`` of the checkout at ``root`` as a module of its own."""
    path = os.path.join(root, "distributed_training_pytorch_tpu", "ops", "pallas.py")
    spec = importlib.util.spec_from_file_location(f"pallas_{abs(hash(root))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_fns(mod, shape, causal, blocks):
    """``{kernel: (fn, args, (bq, bk))}`` on padded ``[B, H, T, D]`` bf16
    operands: the kernels the checkout behind ``mod`` has, all at ``blocks``
    (``(bq, bk, sub)``; a checkout from before ISSUE 32 has no sub-tiles and
    takes ``(bq, bk)``) or, where that is None, each at what the checkout's own
    rule gives."""
    b, h, t, d = shape
    q = k = v = do = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)
    grads = (q, k, v, do, stat, stat)

    def at(kernel):
        if hasattr(mod, "_flash_sub"):
            return blocks or mod._resolve_blocks(kernel, None, None, t, t, causal)
        return (blocks or mod._flash_blocks(kernel, t, t, causal))[:2]

    # kernel -> (the checkout's call, its operands, what it takes between them and the blocks)
    calls = {"fwd": ("_fwd_call", (q, k, v), (t, causal))}
    if hasattr(mod, "_dq_call"):  # a checkout from before ISSUE 30
        calls.update(dq=("_dq_call", grads, (t, t, causal)), dkv=("_dkv_call", grads, (t, t, causal)))
    else:
        calls["bwd"] = ("_bwd_call", grads, (t, causal))

    def bind(kernel, call, between):
        return lambda *operands: getattr(mod, call)(*operands, *between, *at(kernel), False)

    return {kernel: (bind(kernel, call, between), operands, at(kernel))
            for kernel, (call, operands, between) in calls.items()}


def materialize(args):
    out = []
    for i, a in enumerate(args):
        x = jax.random.normal(jax.random.key(i), a.shape, jnp.float32)
        # lse of unit-normal logits sits near log(T); any finite value times the same.
        out.append((x + 5.0 if a.dtype == jnp.float32 else x).astype(a.dtype))
    return out


def row_major(sharding):
    """The kernels' own operand layout, as a format ``jax.jit`` and
    ``jax.device_put`` take."""
    from jax.experimental.layout import Format, Layout

    return Format(Layout(major_to_minor=(0, 1, 2, 3)), sharding)


def time_ms(compiled, args, iters):
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--shapes", default=CELL_SHAPES, help=f"of {','.join(SHAPES)}")
    ap.add_argument("--sub", default="", help="sub-tile sizes of a one-block causal call, e.g. 512,256")
    ap.add_argument("--kernel-layout", action="store_true", help="no layout copies around the kernel")
    ap.add_argument("--out", default="chiprun_out/flash_block_sweep.jsonl")
    args = ap.parse_args()

    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        print(f"flash_block_sweep: needs a TPU, found {jax.default_backend()}", file=sys.stderr)
        return 2

    sides = [("change", load_pallas(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))]
    if args.parent:
        sides.append(("parent", load_pallas(args.parent)))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as sink:
        for name in args.shapes.split(","):
            shape = SHAPES[name]
            for side, mod in sides:
                t = shape[2]
                if side == "parent":
                    grid = [None]
                elif not args.sub:
                    grid = [(bq, bk, None) for bq in BLOCKS for bk in BLOCKS]
                elif t <= max(BLOCKS):  # one block pair: as one tile, in sub-tiles, and as the rule has it
                    grid = [(t, t, None)] + [(t, t, int(sub)) for sub in args.sub.split(",")] + [None]
                else:
                    continue
                for blocks in grid:
                    for kernel, (fn, specs, (bq, bk, *sub)) in kernel_fns(mod, shape, True, blocks).items():
                        if bq > t or bk > t:
                            continue
                        row = {"shape": name, "side": side, "kernel": kernel, "block_q": bq, "block_k": bk,
                               "sub": sub[0] if sub else None, "rule": blocks is None}
                        try:
                            if sharding is not None:
                                specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding) for s in specs]
                            fmt = None
                            if args.kernel_layout:
                                fmt = row_major(sharding or jax.sharding.SingleDeviceSharding(jax.devices()[0]))
                                row["kernel_layout"] = True
                            compiled = jax.jit(fn, in_shardings=fmt, out_shardings=fmt).lower(*specs).compile()
                            if args.compile_only:
                                row["compiled"] = True
                            else:
                                operands = [jax.device_put(x, fmt) if fmt else x for x in materialize(specs)]
                                row["ms"] = round(time_ms(compiled, operands, args.iters), 4)
                                row["device_kind"] = jax.devices()[0].device_kind
                        except Exception as e:  # a refused point is a row of the table, not the end of it
                            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                        print(json.dumps(row), flush=True)
                        sink.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
