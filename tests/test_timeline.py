"""ISSUE 13 tests: unified run timeline, straggler attribution, run doctor,
the data_wait perf-gate ceiling, and the event-schema/doc contracts.

Acceptance pillars:

* the exported timeline is VALID trace-event JSON (stdlib re-parse), every
  lane's spans are monotone and non-overlapping, the async committer gets
  its own track, and the goodput lanes' span durations re-derive the
  meter's bucket seconds exactly;
* straggler sampling observes the run without perturbing it: params and
  ``trace_counts`` bit-identical with ``telemetry=None`` (the historical
  program), and ``Telemetry(straggler=False)`` removes the fields;
* the doctor's verdict rules are deterministic on hand-built run dirs;
* the data_wait gate shares profiling.gate's one rule, with exact
  boundary behavior;
* every event kind the code emits appears in docs/observability.md's
  vocabulary table (doc drift = test failure — the PR 6 AST pattern), and
  every emitted record carries ``schema``/``chips``.
"""

import ast
import json
import math
import os

import jax
import numpy as np
import optax
import pytest
from flax import linen as nn

from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.profiling import gate as gate_lib
from distributed_training_pytorch_tpu.telemetry import (
    SCHEMA_VERSION,
    AnomalyDetector,
    EventLog,
    Telemetry,
    read_events,
)
from distributed_training_pytorch_tpu.telemetry import doctor as doctor_lib
from distributed_training_pytorch_tpu.telemetry import straggler as straggler_lib
from distributed_training_pytorch_tpu.telemetry import timeline as timeline_lib
from distributed_training_pytorch_tpu.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "distributed_training_pytorch_tpu")


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


# ---------------------------------------------------------------------------
# Timeline: hand-built event logs -> trace-event JSON.


def _write_run(tmp_path, records):
    tdir = tmp_path / "telemetry"
    tdir.mkdir(parents=True, exist_ok=True)
    path = tdir / "events.jsonl"
    base = {"t_wall": 0.0, "process": 0, "host": "h", "pid": 7, "chips": "0",
            "schema": SCHEMA_VERSION}
    with open(path, "w", encoding="utf-8") as f:
        for rec in records:
            f.write(json.dumps({**base, **rec}) + "\n")
    return str(tmp_path)


def _goodput(**kw):
    base = {b: 0.0 for b in doctor_lib.BUCKETS}
    base.update(kw)
    return base


def _lane_spans(trace, tid):
    return sorted(
        (e for e in trace["traceEvents"] if e.get("ph") == "X" and e.get("tid") == tid),
        key=lambda e: e["ts"],
    )


def test_timeline_valid_and_monotone(tmp_path):
    run = _write_run(tmp_path, [
        {"event": "run_start", "t_mono": 10.0,
         "goodput_seconds": _goodput()},
        {"event": "compile", "t_mono": 11.0, "epoch": 0, "executables": 1},
        {"event": "window", "t_mono": 12.0, "epoch": 0, "step_in_epoch": 4,
         "steps": 4, "step_ms": 100.0, "live_bytes": 1000},
        # overlapping claim: this window says it took 3s but only 1s passed
        {"event": "window", "t_mono": 13.0, "epoch": 0, "step_in_epoch": 8,
         "steps": 6, "step_ms": 500.0},
        {"event": "epoch_end", "t_mono": 13.5, "epoch": 0, "wall_s": 3.4,
         "steps": 8, "step_ms": 420.0,
         "goodput_seconds": _goodput(productive_step=2.0, compile=1.0,
                                     data_wait=0.4)},
        {"event": "run_end", "t_mono": 14.0,
         "goodput_seconds": _goodput(productive_step=2.2, compile=1.0,
                                     data_wait=0.5, other=0.3)},
    ])
    trace, path = timeline_lib.export_timeline(run)
    with open(path, encoding="utf-8") as f:
        reparsed = json.load(f)  # strict JSON contract
    assert reparsed["traceEvents"]
    # every non-metadata record carries the trace-event schema
    for ev in reparsed["traceEvents"]:
        assert ev["ph"] in ("X", "i", "C", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev and "tid" in ev
    # per-lane monotone, non-overlapping spans (the overlapping window
    # claim above must have been trimmed, not emitted overlapping)
    lanes = {}
    for ev in reparsed["traceEvents"]:
        if ev.get("ph") == "X":
            lanes.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    assert lanes
    for lane in lanes.values():
        lane.sort(key=lambda e: e["ts"])
        for a, b in zip(lane, lane[1:], strict=False):
            assert b["ts"] >= a["ts"] + a["dur"] - 1e-6, (a, b)
    # narrative kinds become markers; counters carry the live bytes
    names = {e["name"] for e in reparsed["traceEvents"]}
    assert {"run_start", "run_end", "compile", "live_bytes"} <= names
    # the original dict and the reparse agree
    assert json.dumps(trace, sort_keys=True) == json.dumps(reparsed, sort_keys=True)


def test_timeline_goodput_spans_rederive_fractions(tmp_path):
    final = _goodput(productive_step=3.0, compile=2.0, data_wait=1.0,
                     checkpoint=0.5, checkpoint_async=0.25, other=0.25)
    run = _write_run(tmp_path, [
        {"event": "run_start", "t_mono": 0.0, "goodput_seconds": _goodput()},
        {"event": "epoch_end", "t_mono": 4.0, "epoch": 0, "wall_s": 4.0,
         "steps": 4, "step_ms": 10.0,
         "goodput_seconds": _goodput(productive_step=1.5, compile=2.0,
                                     data_wait=0.25)},
        {"event": "run_end", "t_mono": 7.0, "goodput_seconds": final},
    ])
    trace, _ = timeline_lib.export_timeline(run)
    derived = timeline_lib.span_bucket_seconds(trace)
    for bucket, want in final.items():
        assert math.isclose(derived[bucket], want, abs_tol=1e-9), bucket
    # fractions re-derive exactly as well
    total = sum(derived.values())
    for bucket, want in final.items():
        assert math.isclose(derived[bucket] / total, want / sum(final.values()),
                            abs_tol=1e-12)


def test_timeline_committer_own_track(tmp_path):
    run = _write_run(tmp_path, [
        {"event": "checkpoint_save", "t_mono": 1.0, "name": "last",
         "mode": "async", "snapshot_ms": 5.0, "epoch": 0},
        {"event": "checkpoint_commit", "t_mono": 2.0, "name": "last",
         "commit_ms": 300.0},
        {"event": "checkpoint_save", "t_mono": 3.0, "name": "best",
         "mode": "sync", "save_ms": 80.0, "epoch": 0},
    ])
    trace, _ = timeline_lib.export_timeline(run)
    ckpt = _lane_spans(trace, timeline_lib.TRACKS["checkpoint"])
    committer = _lane_spans(trace, timeline_lib.TRACKS["committer"])
    assert [s["name"] for s in ckpt] == ["snapshot:last", "save:best"]
    # the committer thread is its own track: queued gap + the commit span
    assert [s["name"] for s in committer] == ["queued:last", "commit:last"]
    queued, commit = committer
    assert math.isclose(commit["dur"], 300.0 * 1e3)
    # queued covers snapshot-end -> commit-start on the one t_mono clock
    assert math.isclose(queued["ts"], 1.0 * 1e6)
    assert math.isclose(queued["ts"] + queued["dur"], commit["ts"])
    # and the sync save's full stall is a span, not an instant
    assert math.isclose(ckpt[1]["dur"], 80.0 * 1e3)


def test_timeline_missing_run_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="telemetry-off"):
        timeline_lib.load_run_events(str(tmp_path))


def test_load_run_events_cites_file_lines_past_torn_records(tmp_path):
    """The doctor's evidence cites FILE lines: a torn fragment (hard-kill
    artifact the tolerant reader skips) must not shift every later
    citation off by one."""
    run = _write_run(tmp_path, [
        {"event": "run_start", "t_mono": 0.0},
        {"event": "window", "t_mono": 1.0, "steps": 2, "step_ms": 1.0},
    ])
    path = os.path.join(run, "telemetry", "events.jsonl")
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"torn fragm\n')  # line 3: malformed
        f.write(json.dumps({"event": "run_end", "t_mono": 2.0,
                            "t_wall": 0.0, "process": 0, "host": "h",
                            "pid": 7}) + "\n")  # line 4
    with pytest.warns(UserWarning, match="malformed"):
        events = timeline_lib.load_run_events(run)
    assert [e["_line"] for e in events] == [1, 2, 4]
    assert events[-1]["event"] == "run_end"


# ---------------------------------------------------------------------------
# Straggler sampling + anomaly kind.


def test_sample_arrivals_multichip(mesh):
    x = jax.device_put(
        np.float32(3.0),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    fields = straggler_lib.sample_arrivals({"loss": x})
    assert fields["chips_sampled"] == 8
    assert fields["chip_skew_ms"] >= 0.0
    assert fields["chip_wall_ms_max"] >= fields["chip_wall_ms_min"]
    assert fields["slowest_chip"] in {d.id for d in mesh.devices.flat}
    assert set(fields) == set(straggler_lib.FIELDS)


def test_sample_arrivals_degrades_to_empty():
    # host scalars / single-shard arrays: nothing to attribute
    assert straggler_lib.sample_arrivals({"loss": 3.0}) == {}
    single = jax.device_put(np.float32(1.0), jax.devices()[0])
    assert straggler_lib.sample_arrivals({"loss": single}) == {}
    assert straggler_lib.sample_arrivals({}) == {}


class _FakeShard:
    """Duck-typed shard whose block_until_ready sleeps — the only way to
    simulate a slow chip on a CPU backend."""

    class _Data:
        def __init__(self, delay):
            self._delay = delay

        def block_until_ready(self):
            import time

            time.sleep(self._delay)

    class _Device:
        def __init__(self, i):
            self.id = i

    def __init__(self, device_id, delay):
        self.device = self._Device(device_id)
        self.data = self._Data(delay)


class _FakeArray:
    def __init__(self, delays):
        self.addressable_shards = [_FakeShard(i, d) for i, d in enumerate(delays)]


def test_sample_arrivals_attributes_the_actually_slow_chip():
    """Incremental-delta attribution: the straggler is named wherever it
    sits in sampling order — including FIRST, where cumulative-elapsed
    timing would bill its tail to every later chip (and report near-zero
    skew with the last chip as 'slowest')."""
    fields = straggler_lib.sample_arrivals({"m": _FakeArray([0.05, 0.0, 0.0, 0.0])})
    assert fields["slowest_chip"] == 0
    assert fields["chip_skew_ms"] > 30.0
    fields = straggler_lib.sample_arrivals({"m": _FakeArray([0.0, 0.0, 0.05, 0.0])})
    assert fields["slowest_chip"] == 2
    assert fields["chip_skew_ms"] > 30.0


def test_straggler_ratio():
    assert straggler_lib.ratio(0.0, 10.0) == 1.0
    assert math.isclose(straggler_lib.ratio(10.0, 10.0), 2.0)
    assert straggler_lib.ratio(-5.0, 10.0) == 1.0  # clock noise clamps


def test_anomaly_straggler_floor_baselined():
    det = AnomalyDetector(warmup=2, straggler=1.5)
    # warmup observations never fire and never set the floor
    assert det.observe(0, straggler_ratio=5.0) == []
    assert det.observe(1, straggler_ratio=5.0) == []
    # first post-warmup observation seeds the floor
    assert det.observe(2, straggler_ratio=1.02) == []
    # under factor x floor: quiet; the floor can only move DOWN
    assert det.observe(3, straggler_ratio=1.4) == []
    found = det.observe(4, straggler_ratio=1.8)
    assert [a.kind for a in found] == ["straggler"]
    assert found[0].baseline == pytest.approx(1.02)
    # absent value never fires (single-chip hosts)
    assert det.observe(5, straggler_ratio=None) == []


# ---------------------------------------------------------------------------
# Doctor: deterministic verdicts on hand-built run dirs.


def _diagnose(tmp_path, records):
    run = _write_run(tmp_path, records)
    return doctor_lib.diagnose(timeline_lib.load_run_events(run))


def test_doctor_healthy(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=9.0, compile=3.0,
                                     data_wait=0.2, checkpoint=0.1)},
    ])
    assert d.healthy and d.verdict == "healthy"
    assert d.to_dict()["steady_fractions"]["compile"] == 0.0


def test_doctor_data_bound(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=2.0, compile=5.0,
                                     data_wait=3.0)},
    ])
    assert d.verdict == "data_bound" and not d.healthy
    top = d.verdicts[0]
    assert top.score == pytest.approx((3.0 / 5.0) / 0.20)
    assert any(r.get("metric") == "data_wait_frac_steady" for r in top.evidence)


def test_doctor_checkpoint_stall(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=2.0, checkpoint=2.0)},
    ])
    assert d.verdict == "checkpoint_stall"


def test_doctor_compile_bound_requires_late_compiles(tmp_path):
    # huge compile fraction alone (warmup) is NOT compile_bound...
    d = _diagnose(tmp_path, [
        {"event": "compile", "t_mono": 1.0, "epoch": 0, "executables": 2},
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=1.0, compile=50.0)},
    ])
    assert d.verdict == "healthy"
    # ...a steady-state retrace is
    d = _diagnose(tmp_path, [
        {"event": "compile", "t_mono": 1.0, "epoch": 2, "executables": 1},
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=5.0, compile=50.0)},
    ])
    assert d.verdict == "compile_bound"
    assert any(r.get("line") == 1 for r in d.verdicts[0].evidence)


def test_doctor_straggler_signals(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "anomaly", "t_mono": 1.0, "kind": "step_time_regression",
         "value": 0.5, "baseline": 0.01, "factor": 2.5},
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=5.0)},
    ])
    assert d.verdict == "straggler"
    # the worst window's ratio alone also fires
    d = _diagnose(tmp_path, [
        {"event": "window", "t_mono": 1.0, "steps": 4, "step_ms": 10.0,
         "straggler_ratio": 2.4, "chip_skew_ms": 14.0},
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=5.0)},
    ])
    assert d.verdict == "straggler"
    assert d.verdicts[0].score == pytest.approx(2.4 / 1.5)


def test_doctor_comm_heavy(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "profile_capture", "t_mono": 1.0, "span_us": 100.0,
         "categories": {"collective": 0.5, "conv": 0.3, "idle": 0.2}},
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=5.0)},
    ])
    assert d.verdict == "comm_heavy"
    assert d.verdicts[0].score == pytest.approx(0.5 / 0.25)


def test_doctor_ranking_most_severe_first(tmp_path):
    d = _diagnose(tmp_path, [
        {"event": "run_end", "t_mono": 9.0,
         "goodput_seconds": _goodput(productive_step=1.0, data_wait=8.0,
                                     checkpoint=5.0)},
    ])
    kinds = [v.kind for v in d.verdicts]
    # both fire; data_wait (8/14)/0.2 outranks checkpoint (5/14)/0.2
    assert kinds == ["data_bound", "checkpoint_stall"]
    assert d.verdicts[0].score > d.verdicts[-1].score


def test_doctor_scalar_fields_match_offline_rules():
    sig = doctor_lib.Signals(
        goodput_seconds=_goodput(productive_step=2.0, data_wait=3.0),
        anomaly_counts={"step_time_regression": 2},
    )
    scores = doctor_lib.scalar_fields(sig)
    assert scores["data_bound"] == pytest.approx((3.0 / 5.0) / 0.20)
    assert scores["straggler"] == pytest.approx(2.0)
    assert scores["healthy"] == 0.0
    quiet = doctor_lib.scalar_fields(doctor_lib.Signals(
        goodput_seconds=_goodput(productive_step=5.0)))
    assert quiet["healthy"] == 1.0 and quiet["data_bound"] == 0.0


def test_steady_fractions_exclude_warmup_buckets():
    fr = doctor_lib.steady_fractions(_goodput(
        productive_step=1.0, compile=97.0, restart_rollback=1.0,
        checkpoint_async=1.0, data_wait=1.0))
    assert fr["compile"] == 0.0 and fr["restart_rollback"] == 0.0
    assert fr["productive_step"] == pytest.approx(0.5)
    assert fr["data_wait"] == pytest.approx(0.5)
    assert doctor_lib.steady_fractions({}) == {b: 0.0 for b in doctor_lib.BUCKETS}


# ---------------------------------------------------------------------------
# data_wait gate: the one rule, boundary-exact.


def test_data_wait_gate_boundary():
    # pass exactly at ceiling*(1+tol); fail epsilon above
    at = gate_lib.check(0.125, 0.10, 0.25, key="k", metric="data_wait_frac")
    assert at.passed
    over = gate_lib.check(0.125 + 1e-9, 0.10, 0.25, key="k", metric="data_wait_frac")
    assert not over.passed
    assert "data_wait_frac" in over.describe()


def test_data_wait_gate_metric_selection_and_stale():
    baseline = {"entries": {"k": {"data_wait_frac": 0.10}},
                "tolerance": {"k": 0.25}}
    res = gate_lib.evaluate(baseline, "k", {"data_wait_frac": 0.01})
    assert res.metric == "data_wait_frac" and res.passed
    # sitting far under a ceiling is healthy, never a stale-baseline nudge
    assert res.stale is False
    # step_per_calib still wins when both sides carry it
    both = {"entries": {"k": {"data_wait_frac": 0.10, "step_per_calib": 1.0}},
            "tolerance": {"k": 0.25}}
    res = gate_lib.evaluate(both, "k",
                            {"data_wait_frac": 0.01, "step_per_calib": 1.1})
    assert res.metric == "step_per_calib"


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_perf_gate_refuses_conflicting_injection_flags():
    """Flag validation happens BEFORE any measurement (the PR 6 rule):
    --data-wait with --inject-slowdown must be an instant argparse error,
    not a post-run KeyError."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_gate.py"),
         "--data-wait", "--inject-slowdown", "3"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 2  # argparse error exit
    assert "--inject-data-wait" in out.stderr


def test_committed_data_wait_baseline_entry():
    """The committed PERF_BASELINE.json carries a usable data-wait ceiling
    (self-parity: the gate could actually gate with it)."""
    baseline = gate_lib.load_baseline()
    entry = baseline["entries"]["data-wait-cpu"]
    assert entry["data_wait_frac"] > 0
    assert "data-wait-cpu" in baseline.get("tolerance", {})
    res = gate_lib.evaluate(baseline, "data-wait-cpu", {"data_wait_frac": 0.01})
    assert res.metric == "data_wait_frac" and res.passed


# ---------------------------------------------------------------------------
# Event schema + vocabulary doc drift (the PR 6 AST-dedup pattern).


def _emitted_event_kinds():
    """AST-scan the package + scripts + bench for ``<events>.emit("kind")``
    call sites (EventLog receivers only: ``events`` / ``_events`` /
    ``event_log`` attributes or a direct ``EventLog(...)`` ctor call —
    analysis/lint.py's unrelated ``self.emit`` never matches)."""
    kinds = {}
    roots = [PKG, os.path.join(REPO, "scripts"), os.path.join(REPO, "bench.py")]
    files = []
    for root in roots:
        if root.endswith(".py"):
            files.append(root)
            continue
        for dirpath, _, names in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            files.extend(os.path.join(dirpath, n) for n in names if n.endswith(".py"))
    for path in files:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit"):
                continue
            recv = node.func.value
            is_log = (
                (isinstance(recv, ast.Attribute)
                 and recv.attr in ("events", "_events", "event_log"))
                or (isinstance(recv, ast.Name)
                    and recv.id in ("events", "_events", "event_log"))
                or (isinstance(recv, ast.Call) and (
                    (isinstance(recv.func, ast.Name) and recv.func.id == "EventLog")
                    or (isinstance(recv.func, ast.Attribute)
                        and recv.func.attr == "EventLog")))
            )
            if not is_log or not node.args:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                kinds.setdefault(first.value, []).append(path)
    return kinds


def test_every_emitted_event_kind_is_documented():
    kinds = _emitted_event_kinds()
    # sanity: the scan actually found the core vocabulary
    assert {"run_start", "window", "checkpoint_save", "anomaly",
            "run_doctor"} <= set(kinds)
    with open(os.path.join(REPO, "docs", "observability.md"), encoding="utf-8") as f:
        table_lines = [ln for ln in f if ln.lstrip().startswith("|")]
    missing = [
        k for k in kinds
        if not any(f"`{k}`" in ln for ln in table_lines)
    ]
    assert not missing, (
        f"event kinds emitted but absent from the docs/observability.md "
        f"vocabulary table: {missing} (emitted at "
        f"{[kinds[k][0] for k in missing]}) — doc drift is a test failure"
    )


def test_every_record_carries_schema_and_chips(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, process_index=0)
    log.emit("run_start", epoch=0)
    log.emit("anomaly", kind="loss_spike")
    log.close()
    records = list(read_events(path))
    assert len(records) == 2
    for rec in records:
        assert rec["schema"] == SCHEMA_VERSION
        assert "chips" in rec and isinstance(rec["chips"], str)


# ---------------------------------------------------------------------------
# Trainer integration: straggler fields on, historical program untouched.


class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(3)(nn.relu(nn.Dense(16)(x)))


class TinyTrainer(Trainer):
    def build_train_dataset(self):
        rng = np.random.RandomState(0)
        labels = rng.randint(0, 3, size=(48,)).astype(np.int32)
        images = (rng.randn(48, 4, 4, 3) + labels[:, None, None, None]).astype(
            np.float32
        )
        return ArrayDataSource(image=images, label=labels)

    def build_model(self):
        return TinyNet()

    def build_criterion(self):
        def crit(logits, batch):
            loss = cross_entropy_loss(logits, batch["label"])
            return loss, {"loss": loss}

        return crit

    def build_optimizer(self, schedule):
        return optax.sgd(schedule)

    def build_scheduler(self):
        return 0.05


class _Quiet:
    def log(self, *a, **k):
        pass


def make_tiny(tmp_path, mesh, **kw):
    defaults = dict(
        max_epoch=2,
        batch_size=8,
        have_validate=False,
        save_best_for=None,
        save_period=None,
        save_folder=str(tmp_path / "runs"),
        num_workers=0,
        log_every=2,
        chain_steps=2,
        async_checkpoint=False,
        mesh=mesh,
        progress=False,
        logger=_Quiet(),
    )
    defaults.update(kw)
    return TinyTrainer(**defaults)


@pytest.fixture(scope="module")
def straggler_run(tmp_path_factory, mesh):
    tmp = tmp_path_factory.mktemp("straggler_run")
    trainer = make_tiny(tmp, mesh, telemetry="on")
    trainer.train()
    events = list(read_events(
        os.path.join(trainer.save_folder, "telemetry", "events.jsonl")))
    return trainer, events


def test_window_events_carry_straggler_fields(straggler_run):
    trainer, events = straggler_run
    windows = [e for e in events if e["event"] == "window"]
    assert windows
    for w in windows:
        assert w["chips_sampled"] == 8
        assert w["chip_skew_ms"] >= 0.0
        assert w["straggler_ratio"] >= 1.0
    # epoch_end carries the last window's skew + the goodput snapshot
    epoch_end = [e for e in events if e["event"] == "epoch_end"][-1]
    assert "chip_skew_ms" in epoch_end
    assert set(epoch_end["goodput_seconds"]) == set(doctor_lib.BUCKETS)
    # run_start anchors the timeline's goodput chain
    assert "goodput_seconds" in events[0] and events[0]["event"] == "run_start"


def test_straggler_off_removes_fields(tmp_path, mesh):
    trainer = make_tiny(tmp_path, mesh, telemetry=Telemetry(straggler=False))
    trainer.train()
    events = list(read_events(
        os.path.join(trainer.save_folder, "telemetry", "events.jsonl")))
    for w in (e for e in events if e["event"] == "window"):
        assert "chip_skew_ms" not in w and "straggler_ratio" not in w


def test_straggler_on_is_historical_program(tmp_path, mesh, straggler_run):
    """THE parity pillar: straggler sampling (and the goodput snapshots /
    doctor counters riding the same syncs) observes the run — trace_counts
    and final params bit-identical with telemetry=None."""
    on, _ = straggler_run
    off = make_tiny(tmp_path, mesh, telemetry=None)
    off.train()
    assert dict(off.engine.trace_counts) == dict(on.engine.trace_counts)
    for a, b in zip(jax.tree.leaves(off.state.params),
                    jax.tree.leaves(on.state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_timeline_exports_from_real_run(straggler_run):
    trainer, _ = straggler_run
    trace, path = timeline_lib.export_timeline(trainer.save_folder)
    with open(path, encoding="utf-8") as f:
        reparsed = json.load(f)
    derived = timeline_lib.span_bucket_seconds(reparsed)
    want = trainer.goodput.to_state()
    total_d, total_w = sum(derived.values()), sum(want.values())
    assert total_d > 0
    for bucket, w in want.items():
        assert abs(derived[bucket] / total_d - w / total_w) < 1e-6, bucket
    # steps lane exists and is monotone
    steps = _lane_spans(reparsed, timeline_lib.TRACKS["steps"])
    assert steps
    for a, b in zip(steps, steps[1:], strict=False):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-6
