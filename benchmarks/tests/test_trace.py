"""The trace reduction on a synthesised trace."""

import pytest

from benchmarks.lib import trace

DEV = "/device:TPU:0"


def _planes():
    ops = [  # (name, label, start_ns, dur_ns)
        ("fusion.1", "fusion.1", 0.0, 100.0),
        ("custom-call.7", "custom-call.7 jit(chained)/_fwd_kernel", 100.0, 50.0),
        ("fusion.2", "fusion.2", 120.0, 80.0),          # overlaps the call by 30
        ("custom-call.9", "custom-call.9 jit(chained)/_bwd_dq_kernel", 400.0, 100.0),
        ("fusion.1", "fusion.1", 900.0, 100.0),
    ]
    host = [("bench.slice", "bench.slice", 0.0, 600.0), ("bench.dispatch", "bench.dispatch", 150.0, 200.0),
            ("something_else", "something_else", 0.0, 1000.0)]
    return [(DEV, [("XLA Ops", ops), ("Steps", [("step", "step", 0.0, 1000.0)])]),
            ("/host:CPU", [("python", host)])]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert trace.union_ns([]) == 0
    assert trace.gaps_ns([(0, 10), (5, 10), (30, 5)], 0, 40) == [(15, 15), (35, 5)]


def test_summary_busy_idle_kernel_time():
    s = trace.summarize(_planes(), window_s=2e-6)
    assert s.busy_s == pytest.approx(400e-9)  # 200 + 100 + 100: the "Steps" line is not an operation
    assert 100.0 * (1 - s.busy_s / s.window_s) == pytest.approx(80.0)
    assert s.op_seconds(("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")) == pytest.approx(150e-9)
    assert s.op_seconds(("no_such_kernel",)) is None
    assert s.top_ops(2) == [["fusion.1", pytest.approx(200e-9)], ["custom-call.9", pytest.approx(100e-9)]]


def test_idle_gaps_named_by_innermost_host_span():
    s = trace.summarize(_planes(), window_s=2e-6)
    gaps = s.top_gaps()
    assert gaps[0] == ["outside_bench_spans", pytest.approx(400e-9)]  # 500..900: only the foreign span covers it
    assert gaps[1] == ["bench.dispatch", pytest.approx(200e-9)]       # 200..400, midpoint inside the dispatch


def test_readers_return_nothing_without_a_kernel():
    import importlib.util
    import os

    from benchmarks.lib import cells

    def reader(name):
        spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    planes = [(DEV, [("XLA Ops", [("fusion.1", "fusion.1", 0.0, 100.0)])])]
    ctx = {"trace": trace.summarize(planes, 1e-6), "cfg": {"family": "lm", "n_embd": 768, "n_layer": 12},
           "traffic": {"seq_len": 1024, "global_batch": 32}, "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_steps": 8, "chips": 1}
    assert reader("flash_roofline").read(ctx) is None
    assert reader("flash_time_share").read(ctx) is None
    assert reader("device_idle_share").read(ctx) == pytest.approx(90.0)
