"""Profiling subsystem tests (ISSUE 6): xplane codec, trace analysis /
device-time attribution, perf-regression gate, hot-path capture, and the
trainer integration's acceptance pillars:

* ``analyze_trace`` category fractions sum to 1 on a checked-in synthetic
  ``.xplane.pb`` fixture with hand-computable attribution (busy/idle split,
  per-category shares, roofline join);
* the report schema (``REPORT_FIELDS``) is stable — consumers (bench JSON,
  ``profile_capture`` events) may rely on the keys across PRs;
* gate pass/fail logic is exact on synthetic baselines, including the
  injected-regression case verify.sh exercises end to end;
* ``Trainer(profile=None)`` reproduces the historical program exactly —
  final params bit-exact and ``TrainEngine.trace_counts`` identical to a
  ``profile=``-on run (the telemetry-off parity convention).

Cost note: trainer tests reuse test_telemetry's TinyTrainer (seconds of CPU
compile); everything else is pure parsing/logic on synthetic bytes.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from distributed_training_pytorch_tpu import profiling
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.profiling import gate as gate_lib
from distributed_training_pytorch_tpu.profiling import xplane
from distributed_training_pytorch_tpu.profiling.capture import StepTraceCapture
from distributed_training_pytorch_tpu.utils import profiling as legacy_profiling

from test_telemetry import assert_trees_equal, make_tiny

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "synthetic_step.xplane.pb")

US = 1_000_000  # picoseconds per microsecond

# The spec behind tests/fixtures/synthetic_step.xplane.pb — five sequential
# critical-path events with one 5us gap at 60us and one at 90us (10us idle
# over a 100us span), one category each, plus an overlapped Async-line event
# the device attribution must ignore. Regenerate the fixture by piping this
# spec through xplane.encode_xspace (test_fixture_bytes_are_encode_xspace
# proves file and spec never drift).
SYNTHETIC_SPEC = [
    {
        "name": "/device:TPU:0",
        "lines": [
            {
                "name": "XLA Ops",
                "timestamp_ns": 0,
                "events": [
                    ("%convolution.1 = f32[8,16,16,8] convolution(%p0, %p1)", 0 * US, 40 * US),
                    ("%fusion.7 = f32[8,16,16,8] fusion(%param.4)", 40 * US, 20 * US),
                    ("%copy.3 = f32[8,8,16,16] copy(%fusion.7)", 65 * US, 10 * US),
                    ("%all-reduce.2 = f32[10] all-reduce(%copy.3)", 75 * US, 15 * US),
                    ("%dot.5 = f32[8,10] dot(%fusion.7, %p2)", 95 * US, 5 * US),
                ],
            },
            {
                "name": "Async XLA Ops",
                "timestamp_ns": 0,
                "events": [("copy-start.9", 0, 100 * US)],
            },
        ],
    }
]

# Exact attribution of the spec: 90us busy over the 100us span, op self-time
# shares scaled by busy_frac 0.9, idle takes the remaining 0.1.
SYNTHETIC_FRACTIONS = {
    "convolution": 0.40,
    "fusion(elementwise)": 0.20,
    "copy/transpose": 0.10,
    "collective": 0.15,
    "matmul": 0.05,
    profiling.IDLE: 0.10,
}


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


# ---------------------------------------------------------------------------
# Legacy utils.profiling surface (the shim must keep the seed behavior).


def test_trace_writes_xplane_and_parser_reads_it(tmp_path):
    with legacy_profiling.trace(str(tmp_path)):
        with legacy_profiling.annotate("tiny_matmul"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    path = legacy_profiling.latest_trace_file(str(tmp_path))
    assert path is not None and path.endswith(".xplane.pb")
    # On the CPU test platform there are no TPU/GPU device planes, so the op
    # table is empty — but the wire-format parse itself must succeed.
    ops = legacy_profiling.top_ops(str(tmp_path))
    assert isinstance(ops, list)
    for name, total_us, count in ops:
        assert isinstance(name, str) and total_us >= 0 and count >= 1


def test_top_ops_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        legacy_profiling.top_ops(str(tmp_path / "nope"))


def test_varint_fields_roundtrip():
    """The hand-rolled protobuf reader handles all wire types it claims."""
    # field 1 varint=300, field 2 bytes"abc", field 3 fixed32, field 4 fixed64
    buf = (
        b"\x08\xac\x02"  # 1<<3|0, varint 300
        b"\x12\x03abc"  # 2<<3|2, len 3
        b"\x1d\x01\x00\x00\x00"  # 3<<3|5
        b"\x21\x02\x00\x00\x00\x00\x00\x00\x00"  # 4<<3|1
    )
    fields = list(xplane._fields(buf))
    assert fields[0] == (1, 0, 300)
    assert fields[1] == (2, 2, b"abc")
    assert fields[2][0] == 3 and len(fields[2][2]) == 4
    assert fields[3][0] == 4 and len(fields[3][2]) == 8


# ---------------------------------------------------------------------------
# xplane codec: the write side must be the read side's exact inverse.


def test_fixture_bytes_are_encode_xspace():
    """The checked-in fixture IS encode_xspace(SYNTHETIC_SPEC) — codec drift
    in either direction (or a stale fixture) fails here byte-for-byte."""
    with open(FIXTURE, "rb") as f:
        assert f.read() == xplane.encode_xspace(SYNTHETIC_SPEC)


def test_encode_read_roundtrip(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(xplane.encode_xspace(SYNTHETIC_SPEC))
    planes = xplane.read_trace(path)
    assert [p.name for p in planes] == ["/device:TPU:0"]
    (plane,) = planes
    assert [ln.name for ln in plane.lines] == ["XLA Ops", "Async XLA Ops"]
    got = [
        (e.name, e.start_ps, e.duration_ps) for e in plane.lines[0].events
    ]
    assert got == list(SYNTHETIC_SPEC[0]["lines"][0]["events"])
    assert plane.lines[0].events[0].end_ps == 40 * US


# ---------------------------------------------------------------------------
# analyze_trace: device-time attribution on the synthetic fixture.


def test_synthetic_attribution_exact():
    prof = profiling.analyze_trace(FIXTURE, steps=5)
    assert prof.source == "device"
    assert prof.span_us == pytest.approx(100.0)
    assert prof.busy_us == pytest.approx(90.0)
    assert prof.idle_us == pytest.approx(10.0)
    assert prof.step_us == pytest.approx(20.0)
    assert prof.device_busy_frac == pytest.approx(0.9)
    assert prof.dispatch_gap_frac == pytest.approx(0.1)
    assert set(prof.categories) == set(SYNTHETIC_FRACTIONS)
    for cat, frac in SYNTHETIC_FRACTIONS.items():
        assert prof.categories[cat] == pytest.approx(frac), cat
    # the overlapped Async-line window never leaks into the attribution
    assert prof.busy_us < 100.0


def test_category_fractions_sum_to_one():
    prof = profiling.analyze_trace(FIXTURE)
    assert math.isclose(sum(prof.categories.values()), 1.0, rel_tol=0, abs_tol=1e-9)


def test_report_schema_stable():
    """to_dict() carries exactly REPORT_FIELDS — the contract bench JSON and
    profile_capture events build on. Additions append to REPORT_FIELDS;
    renames/removals fail here."""
    prof = profiling.analyze_trace(FIXTURE, steps=5)
    d = prof.to_dict()
    assert tuple(d) == profiling.REPORT_FIELDS
    assert json.loads(json.dumps(d)) == d  # event-log/bench serializable
    for row in d["top_ops"]:
        assert {"name", "category", "total_us", "count", "frac_busy"} <= set(row)


def test_roofline_join_lands_on_top_ops():
    flops_by_op = {
        "convolution.1": {"flops": 2.0e9, "bytes": 1.0e7, "arith_intensity": 200.0}
    }
    prof = profiling.analyze_trace(FIXTURE, flops_by_op=flops_by_op)
    by_cat = {row.category: row for row in prof.top_ops}
    conv = by_cat["convolution"]
    assert conv.flops == 2.0e9 and conv.bytes == 1.0e7
    assert conv.arith_intensity == pytest.approx(200.0)
    assert conv.to_dict()["arith_intensity"] == pytest.approx(200.0)
    # unjoined rows (no HLO itemization — fusions etc.) carry None and omit
    # the roofline keys from their dicts
    fusion = by_cat["fusion(elementwise)"]
    assert fusion.flops is None and "flops" not in fusion.to_dict()


def test_host_xla_fallback_uses_interval_union(tmp_path):
    """CPU traces have no device plane: the tf_XLA* runtime threads carry the
    op events. Threads overlap, so busy time is the interval UNION (sum would
    double-count) and runtime bookkeeping noise is excluded."""
    path = str(tmp_path / "host.xplane.pb")
    spec = [
        {
            "name": "/host:CPU",
            "lines": [
                {
                    "name": "tf_XLA_0",
                    "timestamp_ns": 0,
                    "events": [
                        ("dot.1", 0, 50 * US),
                        ("ThreadpoolListener::fire", 0, 100 * US),  # noise
                    ],
                },
                {
                    "name": "tf_XLA_1",
                    "timestamp_ns": 0,
                    # overlaps dot.1 for 25us
                    "events": [("fusion.2", 25 * US, 50 * US)],
                },
            ],
        }
    ]
    with open(path, "wb") as f:
        f.write(xplane.encode_xspace(spec))
    prof = profiling.analyze_trace(path)
    assert prof.source == "host-xla"
    assert prof.span_us == pytest.approx(75.0)
    assert prof.busy_us == pytest.approx(75.0)  # union, not 100us sum
    assert prof.dispatch_gap_frac == pytest.approx(0.0)
    # op self-time splits evenly (50us each) even though threads overlapped
    assert prof.categories["matmul"] == pytest.approx(0.5)
    assert prof.categories["fusion(elementwise)"] == pytest.approx(0.5)
    assert math.isclose(sum(prof.categories.values()), 1.0, abs_tol=1e-9)


def test_async_only_device_plane_never_becomes_critical_path(tmp_path):
    """A TPU window where only async DMA lines carry events (or the op line
    is empty) must raise, not promote overlapped 'Async XLA Ops' spans to
    the critical path — that would fabricate a near-1 busy fraction."""
    for lines in (
        # no "XLA Ops" line at all
        [{"name": "Async XLA Ops", "timestamp_ns": 0, "events": [("copy-start.1", 0, 9 * US)]}],
        # op line present but empty this window
        [
            {"name": "XLA Ops", "timestamp_ns": 0, "events": []},
            {"name": "Async XLA Ops", "timestamp_ns": 0, "events": [("copy-start.1", 0, 9 * US)]},
        ],
    ):
        path = str(tmp_path / "async_only.xplane.pb")
        with open(path, "wb") as f:
            f.write(xplane.encode_xspace([{"name": "/device:TPU:0", "lines": lines}]))
        with pytest.raises(ValueError, match="no XLA op events"):
            profiling.analyze_trace(path)


def test_cross_line_events_rebased_by_line_timestamp(tmp_path):
    """XEvent.offset_ps is line-LOCAL (relative to XLine.timestamp_ns):
    interval analysis across lines must rebase onto the shared trace clock,
    or a thread starting later is misaligned onto the first thread's
    timeline and busy/idle/gap figures are silently wrong."""
    path = str(tmp_path / "skewed.xplane.pb")
    spec = [
        {
            "name": "/host:CPU",
            "lines": [
                {
                    "name": "tf_XLA_0",
                    "timestamp_ns": 0,
                    "events": [("dot.1", 0, 50 * US)],
                },
                {
                    # starts 50us into the trace: its local offset 0 is
                    # absolute 50us — back-to-back with dot.1, NOT overlapped
                    "name": "tf_XLA_1",
                    "timestamp_ns": 50_000,
                    "events": [("fusion.2", 0, 25 * US)],
                },
            ],
        }
    ]
    with open(path, "wb") as f:
        f.write(xplane.encode_xspace(spec))
    prof = profiling.analyze_trace(path)
    # unrebased timelines would union [0,50) with [0,25) -> span/busy 50us
    assert prof.span_us == pytest.approx(75.0)
    assert prof.busy_us == pytest.approx(75.0)
    assert prof.dispatch_gap_frac == pytest.approx(0.0)


def test_multichip_attribution_uses_one_representative_plane(tmp_path):
    """A multi-chip host writes one device plane per chip. Attribution is per
    chip (like step_ms/MFU): pooling N planes would sum op self-time N×
    against one span and count idle only where EVERY chip is simultaneously
    idle — hiding per-chip dispatch gaps. The busiest plane is analyzed."""
    path = str(tmp_path / "multichip.xplane.pb")
    spec = [
        {
            "name": "/device:TPU:0",
            "lines": [
                {
                    "name": "XLA Ops",
                    "timestamp_ns": 0,
                    # 90us self-time over a 100us span: THE representative chip
                    "events": [
                        ("%convolution.1 = f32[8] convolution(%p0, %p1)", 0, 40 * US),
                        ("%dot.5 = f32[8] dot(%p2, %p3)", 50 * US, 50 * US),
                    ],
                },
            ],
        },
        {
            "name": "/device:TPU:1",
            "lines": [
                {
                    "name": "XLA Ops",
                    "timestamp_ns": 0,
                    # 30us self-time, and busy exactly where chip 0 idles —
                    # a pooled union would report zero idle
                    "events": [("%fusion.9 = f32[8] fusion(%p4)", 40 * US, 30 * US)],
                },
            ],
        },
    ]
    with open(path, "wb") as f:
        f.write(xplane.encode_xspace(spec))
    prof = profiling.analyze_trace(path)
    assert prof.source == "device"
    # chip 0 alone: 100us span, 90us busy, the 10us gap at 40us is VISIBLE
    assert prof.span_us == pytest.approx(100.0)
    assert prof.busy_us == pytest.approx(90.0)
    assert prof.dispatch_gap_frac == pytest.approx(0.10)
    # chip 1's fusion never leaks into chip 0's attribution (self-time would
    # otherwise sum to 120us against the 100us span)
    assert "fusion(elementwise)" not in prof.category_us
    assert sum(prof.category_us.values()) == pytest.approx(90.0)
    assert prof.categories["convolution"] == pytest.approx(0.40)
    assert prof.categories["matmul"] == pytest.approx(0.50)
    assert math.isclose(sum(prof.categories.values()), 1.0, abs_tol=1e-9)


def test_analyze_trace_error_contract(tmp_path):
    with pytest.raises(FileNotFoundError):
        profiling.analyze_trace(str(tmp_path))  # no trace under dir
    empty = str(tmp_path / "empty.xplane.pb")
    with open(empty, "wb") as f:
        f.write(xplane.encode_xspace([{"name": "/host:CPU", "lines": []}]))
    with pytest.raises(ValueError, match="no XLA op events"):
        profiling.analyze_trace(empty)
    # a torn write (crashed profiler, disk-full) is ValueError, never a bare
    # IndexError — the type every analysis-failure net (capture, bench)
    # catches, so a corrupt trace degrades to a warning not a dead run
    torn = str(tmp_path / "torn.xplane.pb")
    with open(torn, "wb") as f:
        f.write(b"\x80")  # varint continuation bit with no next byte
    with pytest.raises(ValueError, match="truncated or corrupt"):
        xplane.read_trace(torn)
    with pytest.raises(ValueError):
        profiling.analyze_trace(torn)
    # mid-payload cuts raise too (a Python slice would silently truncate the
    # payload and parse a confidently wrong partial trace) — the fixture is
    # one top-level plane field, so any interior cut lands inside a payload
    with open(FIXTURE, "rb") as f:
        whole = f.read()
    for cut in (len(whole) // 4, len(whole) // 2, len(whole) - 1):
        with open(torn, "wb") as f:
            f.write(whole[:cut])
        with pytest.raises(ValueError, match="truncated or corrupt"):
            xplane.read_trace(torn)


def test_shared_categorizer_is_the_one_source():
    """The dedupe satellite: scripts/profile_step.py no longer carries a
    private categorize(); every category the report emits is in CATEGORIES."""
    import ast

    script = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "profile_step.py"
    )
    with open(script, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    defs = [n.name for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]
    assert "categorize" not in defs  # the CLI is thin: one categorizer, shared
    for name, _, _ in SYNTHETIC_SPEC[0]["lines"][0]["events"]:
        assert profiling.categorize(name) in profiling.CATEGORIES
    assert profiling.IDLE not in profiling.CATEGORIES  # idle is not an op


def test_categorize_matches_instruction_head_not_operands():
    """A full HLO line's operand list must never leak into the bucket: the
    consumer of a conv/collective result is categorized by what IT is —
    otherwise the copy/transpose bucket (the one the dispatch/copy audit
    exists to expose) shrinks into convolution/collective."""
    assert profiling.categorize(
        "%copy.3 = f32[8,8] copy(%convolution.2)"
    ) == "copy/transpose"
    assert profiling.categorize(
        "%fusion.4 = f32[8] fusion(%all-reduce.1), kind=kLoop"
    ) == "fusion(elementwise)"
    assert profiling.categorize(
        "%transpose.7 = f32[8,8] transpose(%reduce-window.2)"
    ) == "copy/transpose"
    # bare trace-event names (no " = ") still bucket by their own head
    assert profiling.categorize("convolution.5") == "convolution"
    assert profiling.categorize("all-reduce.9") == "collective"


# ---------------------------------------------------------------------------
# Perf-regression gate: pure pass/fail logic on synthetic baselines.


def _baseline(tmp_path, *, step_per_calib=2.0, tolerance=0.5):
    path = str(tmp_path / "PERF_BASELINE.json")
    gate_lib.update_baseline(
        path,
        "quick-cpu",
        {"step_ms": 20.0, "calib_ms": 10.0, "step_per_calib": step_per_calib},
        tolerance=tolerance,
    )
    return path


def test_gate_check_boundary_semantics():
    at_tolerance = gate_lib.check(3.0, 2.0, 0.5, key="k", metric="m")
    assert at_tolerance.passed and at_tolerance.ratio == pytest.approx(1.5)
    just_past = gate_lib.check(3.01, 2.0, 0.5, key="k", metric="m")
    assert not just_past.passed
    assert "REGRESSION" in just_past.describe()
    # much faster than baseline = pass, flagged stale (re-record nudge)
    stale = gate_lib.check(0.9, 2.0, 0.5, key="k", metric="m")
    assert stale.passed and stale.stale and "re-record" in stale.describe()
    for bad in ((0.0, 2.0, 0.5), (2.0, 0.0, 0.5), (2.0, 2.0, 0.0)):
        with pytest.raises(ValueError):
            gate_lib.check(*bad, key="k", metric="m")


def test_gate_clean_measurement_passes(tmp_path):
    baseline = gate_lib.load_baseline(_baseline(tmp_path))
    result = gate_lib.evaluate(
        baseline, "quick-cpu", {"step_ms": 21.0, "step_per_calib": 2.1}
    )
    assert result.passed and result.metric == "step_per_calib"
    assert result.tolerance == 0.5  # from the file's tolerance table


def test_gate_injected_regression_fails(tmp_path):
    """The verify.sh self-test case: a 3x injected slowdown must FAIL."""
    baseline = gate_lib.load_baseline(_baseline(tmp_path))
    result = gate_lib.evaluate(
        baseline, "quick-cpu", {"step_ms": 60.0, "step_per_calib": 6.0}
    )
    assert not result.passed and result.ratio == pytest.approx(3.0)


def test_gate_metric_and_tolerance_resolution(tmp_path):
    path = _baseline(tmp_path)
    baseline = gate_lib.load_baseline(path)
    # measurement without the ratio falls back to absolute step_ms
    absolute = gate_lib.evaluate(baseline, "quick-cpu", {"step_ms": 25.0})
    assert absolute.metric == "step_ms" and absolute.passed
    # explicit tolerance beats the file's table
    strict = gate_lib.evaluate(
        baseline, "quick-cpu", {"step_ms": 25.0}, tolerance=0.1
    )
    assert not strict.passed and strict.tolerance == 0.1
    # a tolerance table lost in a merge must NOT soften the gate to some
    # constant: the caller's mode default applies, and with none given the
    # gate refuses to guess
    orphaned = dict(baseline, tolerance={})
    fallback = gate_lib.evaluate(
        orphaned, "quick-cpu", {"step_ms": 25.0}, default_tolerance=0.08
    )
    assert not fallback.passed and fallback.tolerance == 0.08
    with pytest.raises(ValueError, match="no tolerance"):
        gate_lib.evaluate(orphaned, "quick-cpu", {"step_ms": 25.0})


def test_gate_missing_entry_and_malformed_baseline(tmp_path):
    baseline = gate_lib.load_baseline(_baseline(tmp_path))
    with pytest.raises(KeyError, match="no baseline entry"):
        gate_lib.evaluate(baseline, "tpu-v5e", {"step_ms": 1.0})
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    with pytest.raises(ValueError, match="no 'entries' key"):
        gate_lib.load_baseline(str(bad))
    # --update is the documented recovery for a malformed baseline: it must
    # rewrite a fresh file (no-entries AND torn-JSON cases), never crash
    for content in ("{}", "<<<<<<< torn"):
        bad.write_text(content)
        written = gate_lib.update_baseline(
            str(bad), "quick-cpu", {"step_per_calib": 2.0}, tolerance=0.5
        )
        assert written["entries"]["quick-cpu"] == {"step_per_calib": 2.0}
        assert gate_lib.load_baseline(str(bad))["entries"]["quick-cpu"]


def test_gate_update_preserves_other_entries(tmp_path):
    path = _baseline(tmp_path)
    gate_lib.update_baseline(path, "vgg16-tpu", {"step_ms": 77.0}, tolerance=0.08)
    baseline = gate_lib.load_baseline(path)
    assert set(baseline["entries"]) == {"quick-cpu", "vgg16-tpu"}
    assert baseline["tolerance"] == {"quick-cpu": 0.5, "vgg16-tpu": 0.08}
    # re-recording one entry leaves the other (and its tolerance) alone
    gate_lib.update_baseline(path, "quick-cpu", {"step_per_calib": 2.2})
    baseline = gate_lib.load_baseline(path)
    assert baseline["entries"]["vgg16-tpu"] == {"step_ms": 77.0}
    assert baseline["entries"]["quick-cpu"] == {"step_per_calib": 2.2}


def test_committed_baseline_is_wellformed():
    """The repo's PERF_BASELINE.json must always be loadable and carry the
    quick-cpu entry the verify stage gates against."""
    baseline = gate_lib.load_baseline()
    entry = baseline["entries"]["quick-cpu"]
    assert entry["step_per_calib"] > 0
    assert gate_lib.evaluate(baseline, "quick-cpu", entry).passed  # self-parity


# ---------------------------------------------------------------------------
# ProfileConfig / capture state machine.


def test_profile_config_validation():
    with pytest.raises(ValueError, match="steps"):
        profiling.ProfileConfig(steps=0)
    with pytest.raises(ValueError, match="skip_steps"):
        profiling.ProfileConfig(skip_steps=-1)


def test_resolve_profile():
    assert profiling.resolve_profile(None) is None
    assert profiling.resolve_profile(False) is None
    cfg = profiling.resolve_profile("/tmp/traces")
    assert isinstance(cfg, profiling.ProfileConfig) and cfg.dir == "/tmp/traces"
    same = profiling.ProfileConfig(dir="x", steps=3)
    assert profiling.resolve_profile(same) is same
    with pytest.raises(TypeError):
        profiling.resolve_profile(7)


class _Events:
    def __init__(self):
        self.emitted = []

    def emit(self, event, **fields):
        self.emitted.append({"event": event, **fields})


def test_capture_nonzero_rank_never_traces(tmp_path):
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path)), process_index=1
    )
    assert not cap.active and cap.state == "done"
    cap.maybe_start(5)
    cap.maybe_stop(10, force=True)
    assert cap.state == "done" and not os.listdir(tmp_path)


def test_capture_state_machine_skips_compile_and_is_one_shot(tmp_path):
    events = _Events()
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "prof"), steps=2, skip_steps=1),
        log=lambda *a, **k: None,
        events=events,
        process_index=0,
    )
    cap.maybe_start(0)  # step 0 = compile step: below skip prefix
    assert cap.state == "waiting"
    cap.maybe_start(2)  # first boundary past the skip (chained window of 2)
    assert cap.state == "tracing" and cap.start_step == 2
    x = jnp.ones((32, 32))
    jax.block_until_ready(jax.jit(lambda a: a @ a)(x))  # traced device work
    cap.maybe_stop(3)  # 1 of 2 steps covered: keeps tracing
    assert cap.state == "tracing"
    cap.maybe_stop(4)  # window complete
    assert cap.state == "done" and cap.steps_traced == 2
    assert legacy_profiling.latest_trace_file(str(tmp_path / "prof")) is not None
    # one-shot: later boundaries are cheap no-ops
    cap.maybe_start(6)
    assert cap.state == "done"
    # the capture emitted exactly one profile_capture event (with a report
    # summary when CPU-host analysis succeeded, an error field when not)
    kinds = [e["event"] for e in events.emitted]
    assert kinds == ["profile_capture"]
    assert events.emitted[0]["steps"] == 2


def test_capture_force_stop_closes_short_epoch(tmp_path):
    cap = StepTraceCapture(
        profiling.ProfileConfig(
            dir=str(tmp_path / "p"), steps=100, skip_steps=0, analyze=False
        ),
        log=lambda *a, **k: None,
        events=None,
        process_index=0,
    )
    cap.maybe_start(1)
    assert cap.state == "tracing"
    cap.maybe_stop(3)  # 2 of 100: stays open
    assert cap.state == "tracing"
    cap.maybe_stop(3, force=True)  # epoch ended
    assert cap.state == "done" and cap.steps_traced == 2


def test_capture_skip_is_process_local_not_epoch_index(tmp_path):
    """A mid-epoch resume starts at a large epoch-local step index, but the
    resumed process's FIRST dispatched unit still pays XLA compilation — the
    skip prefix must count units this process ran, not trust step_in_epoch."""
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "p"), steps=2, analyze=False),
        log=lambda *a, **k: None,
        events=None,
        process_index=0,
    )
    # resumed at step 40: the first unit (the compile payer) is NOT traced
    cap.maybe_start(40)
    assert cap.state == "waiting"
    cap.maybe_stop(42)  # compile unit completed (chained window of 2)
    cap.maybe_start(42)  # second unit: past the process-local skip prefix
    assert cap.state == "tracing" and cap.start_step == 42
    cap.maybe_stop(44, force=True)
    assert cap.state == "done" and cap.steps_traced == 2


def test_capture_skip_longer_than_epoch_accumulates_across_epochs(tmp_path):
    """skip_steps >= steps-per-epoch must delay the capture into a later
    epoch, not silently never fire (the count does not reset per epoch)."""
    cap = StepTraceCapture(
        profiling.ProfileConfig(
            dir=str(tmp_path / "p"), steps=1, skip_steps=5, analyze=False
        ),
        log=lambda *a, **k: None,
        events=None,
        process_index=0,
    )
    # epoch 1: 4 steps in 2-step windows — all inside the skip prefix
    for s in (0, 2):
        cap.maybe_start(s)
        cap.maybe_stop(s + 2)
    assert cap.state == "waiting"  # 4 of 5 skip steps seen
    # epoch 2: the first window finishes the prefix, the second is traced
    cap.maybe_start(0)
    cap.maybe_stop(2)
    cap.maybe_start(2)
    assert cap.state == "tracing" and cap.start_step == 2
    cap.maybe_stop(4, force=True)
    assert cap.state == "done" and cap.steps_traced == 2


def test_capture_start_failure_never_kills_training(tmp_path, monkeypatch):
    """An unwritable trace dir or an already-active profiler session must
    degrade to a warning that parks the capture in 'done' — the same
    never-kill-training policy the analysis path enforces."""
    warnings = []
    events = _Events()
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "p"), skip_steps=0),
        log=lambda msg, log_type="info": warnings.append((log_type, msg)),
        events=events,
        process_index=0,
    )
    monkeypatch.setattr(
        jax.profiler,
        "start_trace",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("session active")),
    )
    cap.maybe_start(0)  # must not raise
    assert cap.state == "done"
    assert any(t == "warning" for t, _ in warnings)
    assert events.emitted and "error" in events.emitted[0]


def test_capture_abort_stops_session_without_analysis(tmp_path):
    """Exception-path teardown (maybe_stop(abort=True)) must close the
    profiler session WITHOUT paying trace analysis or the roofline probe
    compile — an emergency save racing a preemption grace window cannot
    wait on either. The raw trace still lands on disk."""
    called = []
    events = _Events()
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "p"), steps=100, skip_steps=0),
        log=lambda *a, **k: None,
        events=events,
        process_index=0,
        flops_source=lambda: called.append("probe"),
    )
    cap.maybe_start(0)
    jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((8, 8))))
    cap.maybe_stop(1, force=True, abort=True)
    assert cap.state == "done"
    assert called == [] and cap.report is None  # no probe, no parse
    assert legacy_profiling.latest_trace_file(str(tmp_path / "p")) is not None
    # the raw capture record still lands in the event log
    assert [e["event"] for e in events.emitted] == ["profile_capture"]
    assert "error" not in events.emitted[0]


def test_capture_passes_flops_source_to_analysis(tmp_path, monkeypatch):
    """The roofline join: a completed capture evaluates its lazy flops_source
    and hands the mapping to analyze_trace, so Trainer(profile=...) reports
    carry the documented FLOPs/bytes/intensity columns."""
    from distributed_training_pytorch_tpu.profiling import report as report_mod

    sentinel = {"convolution.1": {"flops": 1e9, "bytes": 1e6, "arith_intensity": 1e3}}
    seen = {}
    real_analyze = report_mod.analyze_trace

    def spy(path, **kw):
        seen.update(kw)
        return real_analyze(FIXTURE, **kw)  # deterministic device-plane trace

    monkeypatch.setattr(report_mod, "analyze_trace", spy)
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "p"), steps=1, skip_steps=0),
        log=lambda *a, **k: None,
        events=None,
        process_index=0,
        flops_source=lambda: sentinel,
    )
    cap.maybe_start(0)
    jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((8, 8))))
    cap.maybe_stop(1)
    assert cap.state == "done"
    assert seen["flops_by_op"] is sentinel
    joined = {r.name: r for r in cap.report.top_ops}
    conv = next(r for n, r in joined.items() if n.startswith("%convolution.1"))
    assert conv.flops == 1e9 and conv.arith_intensity == pytest.approx(1e3)


def test_capture_flops_source_failure_degrades_to_warning(tmp_path):
    """A probe compile that fails (OOM, custom step, lowering error) must
    cost only the roofline columns — the attribution report still lands."""
    warnings = []
    cap = StepTraceCapture(
        profiling.ProfileConfig(dir=str(tmp_path / "p"), steps=1, skip_steps=0),
        log=lambda msg, log_type="info": warnings.append((log_type, msg)),
        events=None,
        process_index=0,
        flops_source=lambda: (_ for _ in ()).throw(RuntimeError("probe failed")),
    )
    cap.maybe_start(0)
    jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((8, 8))))
    cap.maybe_stop(1)  # must not raise
    assert cap.state == "done"
    assert any(t == "warning" and "roofline join" in m for t, m in warnings)


# ---------------------------------------------------------------------------
# Trainer integration: the acceptance pillars.


@pytest.mark.parametrize("profile", [
    lambda tmp: profiling.ProfileConfig(steps=100),  # analyze=True: the default
    # a bare trace dir, as the examples pass PROFILE_DIR (5 steps from step 1:
    # still open at the poisoned step 3)
    lambda tmp: str(tmp / "prof"),
], ids=["config", "dir_string"])
def test_trainer_abort_mid_capture_stops_profiler_session(tmp_path, mesh, profile):
    """An exception with the capture window open (anomaly raise, watchdog)
    must still stop the process-global jax.profiler session — a leaked
    session would fail every later start_trace in this process."""
    from distributed_training_pytorch_tpu.fault import FaultPlan

    plan = FaultPlan().add("nan_loss", epoch=0, step=3)
    trainer = make_tiny(
        tmp_path,
        mesh,
        profile=profile(tmp_path),
        chain_steps=1,
        fault_plan=plan,
        nan_policy="raise",
    )
    with pytest.raises(Exception, match="[Nn]on-finite|nan"):
        trainer.train()
    assert trainer._profile_capture.state == "done"  # closed, not leaked
    # abort teardown skipped analysis: no report, no probe compile paid
    assert trainer._profile_capture.report is None
    # the proof: a fresh trace session starts cleanly afterwards
    with legacy_profiling.trace(str(tmp_path / "after")):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))


def test_trainer_preemption_stops_capture_without_analysis(tmp_path, mesh):
    """A preemption-interrupted epoch is on the emergency-save clock: the
    still-open capture must be force-stopped WITHOUT trace analysis or the
    roofline probe compile (the exception-teardown contract), so the grace
    window goes to the checkpoint, not a report."""
    trainer = make_tiny(
        tmp_path,
        mesh,
        profile=profiling.ProfileConfig(steps=100),  # window outlives the run
        chain_steps=1,
    )
    trainer._preemption_requested = lambda step: step >= 4
    trainer.train()
    assert trainer._epoch_interrupted is True  # the preemption branch ran
    cap = trainer._profile_capture
    assert cap.state == "done"  # session closed, not leaked
    assert cap.report is None  # analysis skipped: no parse, no probe compile
    # the proof the process-global session was released:
    with legacy_profiling.trace(str(tmp_path / "after")):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))


def test_encode_rejects_negative_varint_fields():
    """Arithmetic right-shift floors at -1: a negative timestamp/duration fed
    to the write-side codec must raise, not hang appending 0xFF forever."""
    spec = [{"name": "p", "lines": [{"name": "l", "timestamp_ns": -1, "events": []}]}]
    with pytest.raises(ValueError, match="varint"):
        xplane.encode_xspace(spec)


def test_trainer_flops_index_honest_under_chaining(tmp_path, mesh):
    """chain_steps > 1 traces the chained-scan executable, whose per-module
    instruction numbering does not line up with the single-step probe's — the
    roofline join must be SKIPPED (None), not attach a different
    instruction's flops to a colliding name. Single-step runs keep it."""
    chained = make_tiny(tmp_path, mesh, max_epoch=1, chain_steps=2,
                        telemetry="on", save_folder=str(tmp_path / "c"))
    chained.train()
    assert chained._abstract_batch is not None  # shapes known; gate is chaining
    assert chained._profile_flops_index() is None
    single = make_tiny(tmp_path, mesh, max_epoch=1, chain_steps=1,
                       telemetry="on", save_folder=str(tmp_path / "s"))
    single.train()
    index = single._profile_flops_index()
    assert index and all("flops" in row for row in index.values())


def test_trainer_profile_off_is_the_historical_program(tmp_path, mesh):
    """THE acceptance test: profile=None (the default) and a profile=-on run
    have identical TrainEngine.trace_counts (same compiles, same dispatch
    structure) and bit-exact final params — the capture observes the run at
    unit boundaries, it never alters execution."""
    off = make_tiny(tmp_path / "off", mesh)
    off.train()
    on = make_tiny(
        tmp_path / "on",
        mesh,
        profile=profiling.ProfileConfig(steps=2, analyze=False),
    )
    on.train()
    assert dict(off.engine.trace_counts) == dict(on.engine.trace_counts)
    assert_trees_equal(off.state.params, on.state.params)
    assert_trees_equal(off.state.opt_state, on.state.opt_state)
    # off = historical: no capture object, no profile dir
    assert off._profile_capture is None
    assert not os.path.exists(os.path.join(off.save_folder, "profile"))
    # on actually captured a window of the real chained run into the default
    # <save_folder>/profile location
    cap = on._profile_capture
    assert cap is not None and cap.state == "done" and cap.steps_traced >= 2
    assert legacy_profiling.latest_trace_file(
        os.path.join(on.save_folder, "profile")
    ) is not None
