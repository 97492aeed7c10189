"""Profile a compiled train step: headless per-op device-time table.

Thin CLI over ``distributed_training_pytorch_tpu.profiling`` (ISSUE 6): builds
the exact executable ``bench.py`` times (same model registry, batch, compiler
options), runs a traced window, and prints ``report.analyze_trace``'s
attribution — busy/idle split, category rollup (conv / matmul / fusions /
copies / collectives / reduce / idle), and the top-op table joined with
per-op FLOPs + bytes + arithmetic intensity (roofline position). The
categorizer and the report are the package's — one source of truth shared
with ``Trainer(profile=...)`` captures and bench's ``BENCH_PROFILE`` fields.

Usage:  BENCH_MODEL=resnet50 python scripts/profile_step.py
Env:    PROFILE_STEPS (default 3 traced steps), PROFILE_LIMIT (table rows),
        plus every BENCH_* knob bench.py honors.
"""

import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from distributed_training_pytorch_tpu.profiling import (
    IDLE,
    analyze_trace,
    flops_index,
    top_ops,
    trace,
)
from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng


def main():
    enable_fast_rng()
    steps = int(os.environ.get("PROFILE_STEPS", "3"))
    limit = int(os.environ.get("PROFILE_LIMIT", "40"))

    # Exactly the executable bench.py times (shared builder, same env knobs).
    setup = bench.build_bench_setup(os.environ.get("BENCH_MODEL", "resnet50"))
    model_name, batch, image_size = (
        setup["model_name"], setup["batch"], setup["image_size"]
    )
    engine, state, gbatch = setup["engine"], setup["state"], setup["gbatch"]
    compiled = engine.compile_train_step(
        state, gbatch, compiler_options=setup["compiler_options"]
    )

    # Warm (the first call pays one-time dispatch setup), then trace.
    state, m = compiled(state, gbatch)
    jax.block_until_ready(m)
    log_dir = os.environ.get("PROFILE_DIR") or tempfile.mkdtemp(prefix=f"prof_{model_name}_")
    with trace(log_dir):
        for _ in range(steps):
            state, m = compiled(state, gbatch)
        _ = float(m["loss"])

    report = analyze_trace(
        log_dir, steps=steps, top_k=limit, flops_by_op=flops_index(compiled)
    )
    # The device "Async XLA Ops" line holds overlapped DMA windows — outside
    # the report's critical-path attribution (summing it in would
    # double-count overlap) but worth a line: it is the H2D/prefetch story.
    async_total = sum(t for _, t, _ in top_ops(log_dir, limit=2000, line="Async XLA Ops"))

    print(f"# profile: {model_name} batch={batch} size={image_size} "
          f"steps={steps} (trace {report.trace_path})")
    print(f"# {report.summary()}")
    print(f"# source: {report.source}; busy {report.busy_us/1e3:.2f} ms + idle "
          f"{report.idle_us/1e3:.2f} ms over {report.span_us/1e3:.2f} ms span"
          + (f" = {report.step_us/1e3:.2f} ms/step" if report.step_us else "")
          + (f"  |  async DMA windows (overlapped): {async_total/1e3:.2f} ms"
             if async_total else ""))
    print("\n## category attribution (fractions of span, sum = 1)")
    for cat, frac in sorted(report.categories.items(), key=lambda kv: -kv[1]):
        us = report.category_us.get(cat, report.idle_us if cat == IDLE else 0.0)
        print(f"  {cat:20s} {us/1e3:9.2f} ms  {100*frac:5.1f}%")
    print(f"\n## top {limit} ops (self-time; flops/bytes/intensity where the "
          "HLO walk itemizes them)")
    for row in report.top_ops:
        short = re.sub(r"\s+", " ", row.name)[:120]
        roofline = (
            f"  [{row.flops:.3g} flop / {row.bytes:.3g} B = {row.arith_intensity:.1f} F/B]"
            if row.arith_intensity is not None
            else ""
        )
        print(f"  {row.total_us/1e3:8.2f} ms  x{row.count:<4d} "
              f"{100*row.frac_busy:5.1f}%  {short}{roofline}")


if __name__ == "__main__":
    main()
