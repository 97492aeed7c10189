"""The trace reduction on a synthesised trace."""

import pytest

from benchmarks.lib import trace

DEV = "/device:TPU:0"


def _planes():
    ops = [  # (name, label, start_ns, dur_ns)
        ("fusion.1", "fusion.1", 0.0, 100.0),
        ("flash_fwd.7", "flash_fwd.7 jit(chained)/flash_fwd/pallas_call", 100.0, 50.0),
        ("fusion.2", "fusion.2", 120.0, 80.0),          # overlaps the call by 30
        ("flash_dqkv.9", "flash_dqkv.9 jit(chained)/flash_dqkv/pallas_call", 400.0, 100.0),
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
    assert s.op_seconds(("flash_fwd", "flash_dqkv")) == pytest.approx(150e-9)
    assert s.op_seconds(("no_such_kernel",)) is None
    assert s.top_ops(2) == [["fusion.1", pytest.approx(200e-9)], ["flash_dqkv.9", pytest.approx(100e-9)]]


def test_idle_gaps_named_by_innermost_host_span():
    s = trace.summarize(_planes(), window_s=2e-6)
    gaps = s.top_gaps()
    assert gaps[0] == ["outside_bench_spans", pytest.approx(400e-9)]  # 500..900: only the foreign span covers it
    assert gaps[1] == ["bench.dispatch", pytest.approx(200e-9)]       # 200..400, midpoint inside the dispatch


def reader(name):
    import importlib.util
    import os

    from benchmarks.lib import cells

    spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers_return_nothing_without_a_kernel():
    planes = [(DEV, [("XLA Ops", [("fusion.1", "fusion.1", 0.0, 100.0)])])]
    ctx = {"trace": trace.summarize(planes, 1e-6), "cfg": {"family": "lm", "n_embd": 768, "n_layer": 12},
           "traffic": {"seq_len": 1024, "global_batch": 32}, "peaks": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_steps": 8, "chips": 1}
    assert reader("flash_roofline").read(ctx) is None
    assert reader("flash_time_share").read(ctx) is None
    assert reader("device_idle_share").read(ctx) == pytest.approx(90.0)
    # no collective in the trace (a one-chip cell): the two shares are left out, list or no list
    assert reader("collective_share").read(ctx) is None
    assert reader("collective_exposed_share").read(ctx) is None


# -- collectives --------------------------------------------------------------

AR = "f32[768,768]{1,0:T(8,128)}"


def _hlo(name, opcode, operand="%fusion.1"):
    """An event's name as this runtime gives it: the instruction's whole HLO text."""
    return f"%{name} = {AR} {opcode}({AR} {operand}), channel_id=1, replica_groups=[1,4]<=[4]"


def _op(name, opcode, start, dur, operand="%fusion.1"):
    text = _hlo(name, opcode, operand)
    return (text, text, float(start), float(dur))


def test_which_half_of_a_collective_an_operation_is():
    assert trace.collective_half(_hlo("all-reduce.2", "all-reduce")) == "whole"
    assert trace.collective_half(_hlo("all-reduce-start.1", "all-reduce-start")) == "start"
    assert trace.collective_half(_hlo("all-gather-done.1", "all-gather-done", "%all-gather-start.1")) == "done"
    assert trace.collective_half(_hlo("reduce-scatter-start.3", "async-start")) == "start"
    assert trace.collective_half(_hlo("reduce-scatter-done.3", "async-done", "%reduce-scatter-start.3")) == "done"
    assert trace.collective_half(_hlo("collective-permute.4", "collective-permute")) == "whole"
    for name, opcode in (("fusion.7", "fusion"), ("copy-start.2", "copy-start"), ("slice-done.1", "slice-done"),
                         ("custom-call.9", "custom-call"), ("all-reduce_fusion.1", "fusion"), ("copy-start.5", "async-start")):
        assert trace.collective_half(_hlo(name, opcode)) is None, name


def _collective_planes():
    """Two device planes over a stretch of 1000 ns. Plane 0, the same
    instruction names in two steps: a split all-reduce wholly hidden under a
    fusion, then one half hidden. Plane 1: a synchronous one, exposed, inside
    a `while` container that must not count as cover."""
    plane0 = [
        _op("fusion.1", "fusion", 0, 300),
        _op("all-reduce-start.1", "all-reduce-start", 100, 10),
        _op("fusion.2", "fusion", 110, 80),                                   # covers 110..190
        _op("all-reduce-done.1", "all-reduce-done", 190, 10, "%all-reduce-start.1"),  # in flight 100..200, all under fusion.1
        _op("all-reduce-start.1", "all-reduce-start", 500, 10),               # the next step's, the same name
        _op("fusion.2", "fusion", 510, 45),                                   # covers 510..555
        _op("all-reduce-done.1", "all-reduce-done", 555, 45, "%all-reduce-start.1"),  # in flight 500..600; 50 of it hidden
    ]
    plane1 = [
        _op("while.1", "while", 0, 1000),
        _op("fusion.1", "fusion", 0, 400),
        _op("all-reduce.2", "all-reduce", 400, 100),
        _op("fusion.3", "fusion", 500, 100),
    ]
    return [("/device:TPU:0", [("XLA Ops", plane0)]), ("/device:TPU:1", [("XLA Ops", plane1)])]


def test_collective_time_and_its_exposed_part():
    s = trace.summarize(_collective_planes(), window_s=1e-6)
    collectives, others = trace.collective_intervals(s.devices["/device:TPU:0"])
    assert sorted(collectives) == [(100.0, 100.0), (500.0, 100.0)]
    assert len(others) == 3  # the fusions; no half of a collective
    total, exposed = s.collective_seconds
    assert total == pytest.approx((200 + 100) / 2 * 1e-9)    # plane 0: 100 + 100; plane 1: 100
    # plane 0: nothing of the first, 500..510 and 555..600 of the second; plane 1: all of it
    assert exposed == pytest.approx((55 + 100) / 2 * 1e-9)
    ctx = {"trace": s}
    assert reader("collective_share").read(ctx) == pytest.approx(15.0)
    assert reader("collective_exposed_share").read(ctx) == pytest.approx(7.75)


def test_halves_cut_off_by_the_edge_of_the_trace_count_for_themselves():
    ops = [trace.Op(*_op("all-reduce-done.1", "all-reduce-done", 0, 30, "%all-reduce-start.1")),
           trace.Op(*_op("fusion.1", "fusion", 30, 100)),
           trace.Op(*_op("all-reduce-start.1", "all-reduce-start", 130, 20))]
    collectives, _ = trace.collective_intervals(ops)
    assert sorted(collectives) == [(0.0, 30.0), (130.0, 20.0)]


def test_uncovered_counts_each_stretch_of_the_union_once():
    assert trace.uncovered_ns([(0, 100), (50, 100)], [(20, 10), (120, 100)]) == 110  # 0..150 less 20..30 and 120..150
    assert trace.uncovered_ns([], [(0, 10)]) == 0
