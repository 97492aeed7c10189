"""The reduction of the program's spans over a device trace, on synthesised
spans and operations; the readers end to end on the CPU (no device plane:
the span and counter metrics only); and that BENCHMARK.json and the readers
agree."""

import importlib.util
import json
import os
import tempfile

import pytest

from benchmarks.lib import cells, harness, spans, trace

DEV = "/device:TPU:0"
DATA = os.path.join(os.path.dirname(__file__), "data")
ZERO = 1_700_000_000_000_000_000  # the profiler session's start, realtime ns
NEW = ["idle_in_fetch_share", "idle_in_dispatch_share", "idle_in_glue_share", "stage_ms_per_step",
       "produce_ms_per_step", "ring_empty_share", "loss_head_time_share", "flash_bwd_time_share", "program_load_s"]
COLLECTIVE = ["collective_share", "collective_exposed_share"]  # the four-chip cell's, appended after them


def span(name, lo, hi, thread="MainThread", parent=None, **ids):
    return (name, ZERO + lo, ZERO + hi, thread, parent, ids)


def one_slice(at, fetch_until):
    """A slice of 1000 ns: epoch start 50, a fetch, a dispatch of 100, glue."""
    return [
        span("trainer.train", at, at + 1000),
        span("trainer.epoch", at + 10, at + 990, parent="trainer.train"),
        span("trainer.epoch_start", at + 10, at + 60, parent="trainer.epoch"),
        span("trainer.fetch", at + 60, at + fetch_until, parent="trainer.epoch"),
        span("engine.dispatch", at + fetch_until, at + fetch_until + 100, parent="trainer.epoch", traced=at == 0),
        span("prefetch.stage", at + 20, at + 220, thread="device-prefetch"),
        span("loader.batch", at + 5, at + 105, thread="pool_0"),
        span("loader.batch", at + 5, at + 155, thread="pool_1"),
    ]


def reader(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(cells.BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_self_segments_and_self_time():
    recorded = one_slice(0, 400)
    main = [s for s in recorded if s[3] == "MainThread"]
    segs = [(n, lo - ZERO, hi - ZERO) for n, lo, hi in spans.self_segments(main)]
    assert segs == [
        ("trainer.train", 0, 10), ("trainer.epoch_start", 10, 60), ("trainer.fetch", 60, 400),
        ("engine.dispatch", 400, 500), ("trainer.epoch", 500, 990), ("trainer.train", 990, 1000)]
    own = spans.self_ns(recorded)  # each thread on its own: the workers' spans overlap in time
    assert own == {"trainer.train": 20, "trainer.epoch": 490, "trainer.epoch_start": 50, "trainer.fetch": 340,
                   "engine.dispatch": 100, "prefetch.stage": 200, "loader.batch": 250}


def test_slices_are_picked_from_the_roots():
    recorded = [s for k in range(5) for s in one_slice(2000 * k, 400)]
    traced, window = spans.pick_slices(recorded, window_slices=2, trace_slices=1)
    assert [r[1] - ZERO for r in traced] == [4000] and [r[1] - ZERO for r in window] == [6000, 8000]
    traced, window = spans.pick_slices(recorded, window_slices=1, trace_slices=2)
    assert [r[1] - ZERO for r in traced] == [4000, 6000]
    assert spans.pick_slices(recorded, window_slices=4, trace_slices=2) is None  # five roots cannot hold six slices
    assert spans.pick_slices(recorded, window_slices=0, trace_slices=1) is None


def test_gaps_are_split_by_overlap_not_by_midpoint():
    segments = [("a", 0, 100), ("b", 100, 400), ("c", 450, 500)]
    # 90..130: 10 under a, 30 under b (the midpoint, 110, is under b); 380..470: 20 b, 50 nothing, 20 c
    assert spans.split_gaps([(90, 40), (380, 90)], segments) == {"a": 10, "b": 50, "c": 20, spans.OUTSIDE: 50}
    assert spans.split_gaps([], segments) == {}


def traced_stretch(ops, host=(), window_ns=2000):
    return trace.summarize([(DEV, [("XLA Ops", ops)]), ("/host:CPU", [("python", list(host))])], window_ns / 1e9)


def test_the_three_shares_sum_to_the_idle_share():
    # two traced slices; the device works 100..350 and 500..900 of the first, 1450..1950 of the second
    recorded = one_slice(0, 400) + one_slice(1000, 450) + one_slice(2000, 400)
    ops = [("op", "op", 100.0, 250.0), ("op", "op", 500.0, 400.0), ("op", "op", 1450.0, 500.0)]
    summary = traced_stretch(ops)
    traced, _ = spans.pick_slices(recorded, window_slices=1, trace_slices=2)
    by_span = spans.lay_over(recorded, traced, summary, ZERO)
    # idle: 0..100, 350..500, 900..1450, 1950..2000
    assert {k: round(v * 1e9) for k, v in by_span.items()} == {
        "trainer.train": 10 + 10 + 10 + 10,     # 0..10, 990..1000, 1000..1010, 1990..2000
        "trainer.epoch_start": 50 + 50,         # 10..60, 1010..1060
        "trainer.fetch": 40 + 50 + 390,         # 60..100, 350..400, 1060..1450
        "engine.dispatch": 100,                 # 400..500
        "trainer.epoch": 90 + 40,               # 900..990, 1950..1990
    }
    shares = spans.group_idle(by_span)
    assert {k: round(v * 1e9) for k, v in shares.items()} == {"fetch": 480, "dispatch": 100, "glue": 270}
    idle_share = 100.0 * (1.0 - summary.busy_s / summary.window_s)  # device_idle_share's own arithmetic
    assert 100.0 * sum(shares.values()) / summary.window_s == pytest.approx(idle_share) == pytest.approx(42.5)


@pytest.mark.parametrize("ops,host", [
    # an operation that starts 2 ms before the first traced root: another clock
    ([("op", "op", -2e6, 100.0)], ()),
    # one that ends 2 ms after the stretch
    ([("op", "op", 100.0, 2e6 + 2000)], ()),
    # no operation at all
    ([], ()),
    # the harness's slice span is on the trace's clock and the root is not inside it
    ([("op", "op", 100.0, 100.0)], [("bench.slice", "", 5e6, 1000.0), ("bench.slice", "", 5e6 + 1000, 1000.0)]),
    # two slice spans around one root (the slack of a millisecond takes in both)
    ([("op", "op", 100.0, 100.0)], [("bench.slice", "", 0.0, 1000.0), ("bench.slice", "", 1000.0, 1000.0)]),
])
def test_no_number_without_a_shared_clock(ops, host):
    recorded = one_slice(0, 400) + one_slice(1000, 450) + one_slice(2000, 400)
    traced, _ = spans.pick_slices(recorded, window_slices=1, trace_slices=2)
    assert spans.lay_over(recorded, traced, traced_stretch(ops, host), ZERO) is None


def test_the_harness_slice_spans_confirm_the_clock():
    recorded = one_slice(0, 400) + one_slice(1000, 450) + one_slice(2000, 400)
    traced, _ = spans.pick_slices(recorded, window_slices=1, trace_slices=2)
    host = [("bench.slice", "", -30.0, 1040.0), ("bench.slice", "", 1000.0 - 5, 1010.0)]
    stretch = traced_stretch([("op", "op", 100.0, 1800.0)], host)
    by_span = spans.lay_over(recorded, traced, stretch, ZERO, slack=50)
    assert round(sum(by_span.values()) * 1e9) == 200
    assert spans.lay_over(recorded, traced, stretch, ZERO, slack=20) is None  # the first slice span opens 30 early


HEAD_OPS = [  # (name = the instruction's HLO text, label, start, duration), and each one's tf_op
    ("%while.1 = (f32[8]) while(%tuple), body=%b", "jit(step)/jvp(loss_head)/while", 0.0, 1000.0),
    ("%fusion.1 = f32[8] fusion(%p), kind=kLoop", "jit(step)/jvp(loss_head)/while/body/dot_general", 0.0, 400.0),
    ("%fusion.2 = f32[8] fusion(%p), kind=kLoop", "jit(step)/transpose(jvp(loss_head))/while/body/mul", 300.0, 300.0),
    ("%fusion.3 = f32[8] fusion(%p), kind=kLoop", "jit(step)/optimizer/add", 1000.0, 50.0),
    ("%flash_dqkv.7 = bf16[8] custom-call(%q), custom_call_target=\"tpu_custom_call\"", "jit(step)/flash_dqkv/pallas_call", 2000.0, 70.0),
    ("%flash_dqkv.8 = bf16[8] custom-call(%q), custom_call_target=\"tpu_custom_call\"", None, 2100.0, 30.0),
    ("%flash_fwd.9 = bf16[8] custom-call(%q), custom_call_target=\"tpu_custom_call\"", "jit(step)/flash_fwd/pallas_call", 2200.0, 40.0),
]


def test_scoped_device_time_leaves_containers_out_and_counts_overlaps_once():
    summary = trace.summarize([(DEV, [("XLA Ops", [(n, n + " 1.0", s, d) for n, _, s, d in HEAD_OPS])])], 1e-5)
    scopes = {n: scope for n, scope, _, _ in HEAD_OPS if scope}
    # the two fusions overlap by 100; the while that holds them is a container
    assert spans.plane_mean_seconds(summary.devices, ("loss_head",), scopes) == pytest.approx(600e-9)
    assert spans.plane_mean_seconds(summary.devices, ("loss_head",)) is None  # the scope is in no label
    # a kernel's name is in the instruction's own name, with or without the scopes
    assert spans.plane_mean_seconds(summary.devices, ("flash_dqkv",), scopes) == pytest.approx(100e-9)
    assert spans.plane_mean_seconds(summary.devices, ("flash_dqkv",)) == pytest.approx(100e-9)
    assert spans.plane_mean_seconds(summary.devices, ("no_such_scope",), scopes) is None


# -- a synthesised xplane: just the fields benchmarks/lib/xplane.py reads ----------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name, stat_names, event_metadata=(), stats=(), lines=()):
    body = field(2, name)
    for i, stat in enumerate(stat_names, 1):
        body += field(5, field(1, i) + field(2, field(1, i) + field(2, stat)))
    for i, (event_name, event_stats) in enumerate(event_metadata, 1):
        meta = field(1, i) + field(2, event_name) + b"".join(field(5, st) for st in event_stats)
        body += field(4, field(1, i) + field(2, meta))
    body += b"".join(field(6, st) for st in stats) + b"".join(field(3, ln) for ln in lines)
    return field(1, body)


def write_xplane(path, start_ns, stop_ns):
    names = ["hlo_category", "tf_op", "a ref value"]
    events = []
    for i, (name, scope, _, _) in enumerate(HEAD_OPS):
        stats = [field(1, 1) + field(5, "fusion")]
        if scope and i % 2:
            stats.append(field(1, 2) + field(5, scope))  # tf_op as a string
        elif scope:
            names.append(scope)
            stats.append(field(1, 2) + field(7, len(names)))  # tf_op as a reference to a stat name
        events.append((name, stats))
    device = plane(DEV, names, events, lines=[field(2, "XLA Ops") + field(4, field(1, 1) + field(2, 5) + field(3, 7))])
    env = plane("Task Environment", ["profile_start_time", "profile_stop_time"],
                stats=[field(1, 1) + field(3, start_ns), field(1, 2) + field(3, stop_ns)])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(plane("/host:CPU", ["x"]) + device + env)


def test_the_session_and_the_scopes_are_read_from_the_xplane(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    write_xplane(str(tmp_path / "bench_run_a" / "trace" / "plugins" / "profile" / "t0" / "h.xplane.pb"), ZERO, ZERO + 5000)
    write_xplane(str(tmp_path / "bench_run_b" / "trace" / "plugins" / "profile" / "t1" / "h.xplane.pb"), ZERO + 9000, ZERO + 9900)
    found = spans.find_session(ZERO + 100)  # by the moment, not by the newest file
    assert (found.start_ns, found.stop_ns) == (ZERO, ZERO + 5000)
    assert found.scopes == {n: scope for n, scope, _, _ in HEAD_OPS if scope}
    assert spans.find_session(ZERO + 9500).start_ns == ZERO + 9000
    assert spans.find_session(ZERO + 7000) is None and spans.find_session().start_ns in (ZERO, ZERO + 9000)
    # the two scope readers, through ctx
    summary = trace.summarize([(DEV, [("XLA Ops", [(n, n + " 1.0", s, d) for n, _, s, d in HEAD_OPS])])], 1e-5)
    ctx = {"trace": summary, "steps": 8, "traffic": {"steps_per_epoch": 8, "trace_slices": 1}}
    assert reader("loss_head_time_share").read(ctx) == pytest.approx(6.0)
    assert reader("flash_bwd_time_share").read(ctx) == pytest.approx(1.0)
    assert reader("loss_head_time_share").read({"trace": None}) is None


def test_the_session_start_is_read_from_a_real_trace_file(tmp_path, monkeypatch):
    """A real (CPU) profiler session under <TMPDIR>/bench_run_*/trace: its
    start is found by a moment inside it, and by no moment outside it."""
    import time

    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    logdir = tmp_path / "bench_run_test" / "trace"
    before = time.time_ns()
    jax.profiler.start_trace(str(logdir))
    inside = time.time_ns()
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    jax.profiler.stop_trace()
    found = spans.find_session(inside)
    assert found is not None and before <= found.start_ns <= inside <= found.stop_ns
    assert spans.find_session(before - 10**9) is None and spans.find_session(time.time_ns() + 10**9) is None


def test_every_new_per_layer_entry_has_its_reader_and_says_the_same():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW + COLLECTIVE):] == NEW + COLLECTIVE  # appended, in this order
    cell_names = [w["name"] for w in bench["workloads"]]
    for name in NEW + COLLECTIVE:
        entry = dict(entries[name])
        workloads = entry.pop("workloads", None)
        assert reader(name).DECLARATION == entry, name
        assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
        if name in ("loss_head_time_share", "flash_bwd_time_share"):
            assert workloads == ["gpt2s_t1024", "gpt2s_t4096", "gpt2s_t1024_dp4"] and set(workloads) <= set(cell_names)
        elif name in COLLECTIVE:
            assert workloads == ["gpt2s_t1024_dp4"]
        else:
            assert workloads is None


def bench_with(tmp_path, names):
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    bench["per_layer"] += [reader(n).DECLARATION for n in names]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_a_traced_cpu_run_reports_the_span_and_counter_metrics(tmp_path):
    """End to end through the harness: the recorder is installed because the
    traced run turns telemetry on, the readers find the window's slices among
    the roots, and what needs a device plane is left out (a CPU trace has none)."""
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()
    try:
        line = harness.run_cell("vgg_tiny", 2**31 + 17, 0.5, True, require_tpu=False,
                                bench_file=bench_with(tmp_path, NEW), data_dirs=[DATA])
        assert line["correct"] is True
        got = {k: v["value"] for k, v in line["metrics"].items() if k in NEW}
        assert sorted(got) == sorted(["stage_ms_per_step", "produce_ms_per_step", "ring_empty_share", "program_load_s"])
        assert got["stage_ms_per_step"] > 0 and got["produce_ms_per_step"] > 0 and got["program_load_s"] > 0
        assert 0 <= got["ring_empty_share"] <= 100
        # the first slice traced every program; the window none
        roots = spans.roots(profiling.recorded())
        traced_at = [s.start_ns for s in profiling.recorded() if s.name == "engine.dispatch" and s.ids["traced"]]
        assert traced_at and all(roots[0].start_ns < t < roots[0].end_ns for t in traced_at)
    finally:
        profiling.uninstall_recorder()


def test_an_untraced_run_keeps_no_spans(tmp_path):
    from distributed_training_pytorch_tpu import profiling

    profiling.uninstall_recorder()
    harness.run_cell("vgg_tiny", 2**31 + 18, 0.5, False, require_tpu=False,
                     bench_file=os.path.join(DATA, "BENCHMARK.json"), data_dirs=[DATA])
    assert profiling.recorded() == []


def test_the_readers_return_nothing_on_a_program_without_spans(monkeypatch):
    """The parent commit's `profiling` has no `recorded`: every reader of the
    program's spans returns None and raises nothing."""
    from distributed_training_pytorch_tpu import profiling

    monkeypatch.delattr(profiling, "recorded")
    summary = trace.summarize([(DEV, [("XLA Ops", [("fusion.1", "fusion.1", 0.0, 100.0)])])], 1e-6)
    for name in NEW:
        ctx = {"trace": summary, "steps": 8, "traffic": {"steps_per_epoch": 8, "trace_slices": 1}}
        assert reader(name).read(ctx) is None, name
