"""Measure the fused 1x1-conv+BN-apply+ReLU Pallas kernel against XLA's own
fusion on ResNet-50 stage-1 shapes.

Stage-1's 1x1 convs are pure GEMMs at ~28 FLOP/byte (bandwidth-bound on a
240 FLOP/byte v5e), so a hand-tiled Pallas GEMM+epilogue either moves more
bytes/s than XLA's conv fusion or it measurably cannot. This script produces
that measurement; it has not been run on today's chip (ROADMAP S2(c), S4).

Method: each candidate computes relu((x . w) * a + b) on NHWC stage-1
shapes; timing is a lax.scan chain of STEPS calls (one dispatch per window
— per-call host latency never lands inside the window), best of WINDOWS
windows, with the weight perturbed per trip by the
carried output statistic so no iteration is loop-invariant. The bandwidth
floor (read x + write y at 819 GB/s) anchors every number.

Usage: python scripts/resnet_pallas_probe.py   (env: STEPS, WINDOWS, BATCH)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act
from distributed_training_pytorch_tpu.train.autotune import time_chained

HBM_BYTES_PER_S = 819e9  # v5e
STEPS = int(os.environ.get("STEPS", "20"))
WINDOWS = int(os.environ.get("WINDOWS", "4"))
BATCH = int(os.environ.get("BATCH", "256"))


def xla_conv(x, w, a, b, relu=True):
    """The model's formulation: 1x1 conv_general_dilated + affine + relu —
    what XLA fuses in the real step (models/resnet.py BottleneckBlock)."""
    z = jax.lax.conv_general_dilated(
        x, w.reshape(1, 1, *w.shape), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )
    y = z * a + b
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def xla_dot(x, w, a, b, relu=True):
    """Same math as a flattened dot — rules out conv-vs-dot lowering as the
    variable."""
    lead = x.shape[:-1]
    z = jnp.dot(x.reshape(-1, x.shape[-1]), w, preferred_element_type=jnp.float32)
    y = z * a + b
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype).reshape(*lead, w.shape[1])


def pallas_fused(block_rows):
    def f(x, w, a, b, relu=True):
        return conv1x1_bn_act(x, w, a, b, relu=relu, block_rows=block_rows)

    return f


# Timing: train.autotune.time_chained — the ONE two-length-differencing
# scan-chain timer, now shared with the autotuner's candidate measurement
# (ISSUE 17 moved it there; tests/test_autotune.py AST-enforces that this
# probe keeps no private copy). Semantics unchanged: per-call seconds as
# (t_long - t_short) / extra_trips with the weight (arg 1) perturbed per
# trip by the carried output statistic.


def main():
    results = []
    shapes = [(64, 256, "stage1 expand 56x56x64->256"),
              (256, 64, "stage1 reduce 56x56x256->64")]
    only = os.environ.get("SHAPE")  # "expand" | "reduce" — rerun one shape
    if only:
        shapes = [sh for sh in shapes if only in sh[2]]
    for cin, cout, tag in shapes:
        # Generate ON DEVICE: no 100-400 MB host array to ship.
        @jax.jit
        def gen(key):
            kx, kw, ka, kb = jax.random.split(key, 4)
            return (
                jax.random.normal(kx, (BATCH, 56, 56, cin), jnp.bfloat16),
                jax.random.normal(kw, (cin, cout), jnp.bfloat16) * 0.05,
                jax.random.uniform(ka, (cout,), jnp.float32) + 0.5,
                jax.random.normal(kb, (cout,), jnp.float32),
            )

        x, w, a, b = gen(jax.random.key(0))
        n = BATCH * 56 * 56
        bytes_moved = n * (cin + cout) * 2  # read x + write y, bf16
        floor_ms = bytes_moved / HBM_BYTES_PER_S * 1e3

        row = {"shape": tag, "floor_ms": round(floor_ms, 3)}
        cands = {"xla_conv": xla_conv, "xla_dot": xla_dot}
        for br in (1024, 2048):
            cands[f"pallas_b{br}"] = pallas_fused(br)
        err_of = jax.jit(
            lambda got, x, w, a, b: jnp.max(
                jnp.abs(got.astype(jnp.float32) - xla_conv(x, w, a, b).astype(jnp.float32))
            )
        )
        for name, f in cands.items():
            # error computed on device: only a scalar comes back
            err = float(err_of(jax.jit(f)(x, w, a, b), x, w, a, b))
            dt = time_chained(f, x, w, a, b, steps=STEPS, windows=WINDOWS)
            row[name] = {
                "ms": round(dt * 1e3, 3),
                "pct_of_bw_floor": round(floor_ms / (dt * 1e3) * 100, 1),
                "max_abs_err_vs_conv": err,
            }
            print(f"{tag:36s} {name:12s} {dt*1e3:7.3f} ms "
                  f"({floor_ms/(dt*1e3)*100:5.1f}% of BW floor, err {err:.3g})",
                  flush=True)
        results.append(row)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
