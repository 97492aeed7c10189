"""ISSUE 15 tests: live run monitoring — the shared incremental event
reader, the streaming doctor + liveness contract, debounced alert rules,
the heartbeat pulse, and the in-process status exporter.

Acceptance pillars:

* ONE reader: ``events.EventFollower`` behind both ``load_run_events``
  and the monitor's tail (timeline owns no private parser — AST-enforced),
  torn-final-line tolerance and ``_line`` citations preserved;
* ONE verdict engine: ``doctor.update_signals`` folded incrementally
  produces byte-identical diagnoses to the post-hoc ``extract_signals``
  path on the same log;
* liveness: training / stale_heartbeat / dead / finished from file
  freshness + heartbeat content alone (fake clock), watchdog patrol
  heartbeats carrying ``since_progress_s``;
* alerts: debounced (fire on false->true, re-arm on clear), min-steady
  guard, ``monitor_alert`` records;
* exporter: ``/status`` JSON + ``/metrics`` valid Prometheus text under
  concurrent requests, port-in-use degrades to a warning, teardown
  releases the port, and an ``export_port=`` run is bit-exact
  (params + trace_counts) with the exporter off — the historical-program
  pillar.
"""

import ast
import json
import os
import re
import socket
import threading
import time
import urllib.request

import jax
import numpy as np
import optax
import pytest
from flax import linen as nn

from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.fault.watchdog import StepWatchdog
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.telemetry import (
    EventFollower,
    EventLog,
    Telemetry,
    load_run_events,
)
from distributed_training_pytorch_tpu.telemetry import doctor as doctor_lib
from distributed_training_pytorch_tpu.telemetry import events as events_lib
from distributed_training_pytorch_tpu.telemetry import timeline as timeline_lib
from distributed_training_pytorch_tpu.telemetry.exporter import (
    StatusExporter,
    prometheus_text,
)
from distributed_training_pytorch_tpu.telemetry.monitor import (
    AlertConfig,
    RunMonitor,
    worst_exit_code,
)
from distributed_training_pytorch_tpu.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_lines(path, lines):
    with open(path, "w", encoding="utf-8") as f:
        f.write("".join(lines))


def _append(path, text):
    with open(path, "a", encoding="utf-8") as f:
        f.write(text)


def _rec(event, **fields):
    return json.dumps({"event": event, **fields}) + "\n"


# ---------------------------------------------------------------------------
# EventFollower: the ONE incremental reader.


def test_follower_incremental_polls(tmp_path):
    path = str(tmp_path / "e.jsonl")
    f = EventFollower(path)
    assert f.poll() == []  # not written yet: the monitor may attach early
    _write_lines(path, [_rec("run_start", t_mono=1.0)])
    got = f.poll()
    assert [r["event"] for r in got] == ["run_start"]
    assert f.poll() == []  # nothing new
    _append(path, _rec("window", t_mono=2.0) + _rec("epoch_end", t_mono=3.0))
    assert [r["event"] for r in f.poll()] == ["window", "epoch_end"]


def test_follower_withholds_torn_tail_until_complete(tmp_path):
    path = str(tmp_path / "e.jsonl")
    _write_lines(path, [_rec("run_start", t_mono=1.0), '{"event": "win'])
    f = EventFollower(path)
    assert [r["event"] for r in f.poll()] == ["run_start"]
    _append(path, 'dow", "t_mono": 2.0}\n')
    got = f.poll()
    assert [r["event"] for r in got] == ["window"]
    assert got[0]["_line"] == 2  # the completed line, cited correctly


def test_follower_final_parses_unterminated_tail(tmp_path):
    # A killed writer's last COMPLETE record missing only its newline is
    # data on a post-mortem read; a torn fragment warns and skips (the
    # read_events(strict=False) contract).
    path = str(tmp_path / "e.jsonl")
    _write_lines(path, [_rec("run_start", t_mono=1.0),
                        '{"event": "window", "t_mono": 2.0}'])
    f = EventFollower(path)
    assert [r["event"] for r in f.poll()] == ["run_start"]
    assert [r["event"] for r in f.poll(final=True)] == ["window"]
    torn = str(tmp_path / "torn.jsonl")
    _write_lines(torn, [_rec("run_start", t_mono=1.0), '{"to'])
    f2 = EventFollower(torn)
    with pytest.warns(UserWarning, match="malformed"):
        got = f2.poll(final=True)
    assert [r["event"] for r in got] == ["run_start"]


def test_follower_resets_on_truncation(tmp_path):
    path = str(tmp_path / "e.jsonl")
    _write_lines(path, [_rec("run_start", t_mono=1.0), _rec("window", t_mono=2.0)])
    f = EventFollower(path)
    assert len(f.poll()) == 2
    _write_lines(path, [_rec("run_start", t_mono=9.0)])  # fresh attempt, smaller
    got = f.poll()
    assert [r["event"] for r in got] == ["run_start"]
    assert got[0]["t_mono"] == 9.0 and got[0]["_line"] == 1


def test_follower_line_citations_stable_past_blank_and_malformed(tmp_path):
    path = str(tmp_path / "e.jsonl")
    _write_lines(path, [
        _rec("run_start", t_mono=1.0),
        "\n",
        "not json\n",
        _rec("window", t_mono=2.0),
    ])
    with pytest.warns(UserWarning, match="malformed"):
        recs = load_run_events(path)
    assert [(r["event"], r["_line"]) for r in recs] == [
        ("run_start", 1), ("window", 4)]


def test_load_run_events_equals_incremental_accumulation(tmp_path):
    path = str(tmp_path / "e.jsonl")
    lines = [_rec("run_start", t_mono=1.0), _rec("window", t_mono=2.0),
             _rec("run_end", t_mono=3.0)]
    _write_lines(path, lines[:1])
    f = EventFollower(path)
    acc = f.poll()
    _append(path, "".join(lines[1:]))
    acc += f.poll(final=True)
    assert acc == load_run_events(path)


def test_follower_final_tail_not_consumed_on_resurrection(tmp_path):
    """A 'dead' verdict's final poll must not destroy the tail: if the
    writer was only stalled and resumes, the completed line is read
    normally (no lost record, no duplicate, no drifted _line)."""
    path = str(tmp_path / "e.jsonl")
    # complete record missing only its newline: final-yielded, then deduped
    # when the newline lands
    _write_lines(path, [_rec("run_start", t_mono=1.0),
                        '{"event": "window", "t_mono": 2.0}'])
    f = EventFollower(path)
    f.poll()
    assert [r["event"] for r in f.poll(final=True)] == ["window"]
    _append(path, "\n" + _rec("epoch_end", t_mono=3.0))
    got = f.poll()
    assert [(r["event"], r["_line"]) for r in got] == [("epoch_end", 3)]
    # a TORN fragment at final poll: withheld (not consumed), so the
    # resumed writer's continuation completes it into a real record
    torn = str(tmp_path / "torn.jsonl")
    _write_lines(torn, [_rec("run_start", t_mono=1.0), '{"event": "win'])
    f2 = EventFollower(torn)
    f2.poll()
    with pytest.warns(UserWarning, match="malformed"):
        assert f2.poll(final=True) == []
    _append(torn, 'dow", "t_mono": 2.0}\n')
    assert [(r["event"], r["_line"]) for r in f2.poll()] == [("window", 2)]


def test_load_run_events_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="telemetry-off"):
        load_run_events(str(tmp_path / "nope"))


def test_timeline_owns_no_private_parser():
    """Satellite contract: the timeline re-exports the shared reader and
    holds NO parsing of its own — no json.loads, no read_events call, no
    open-for-read of the log (AST-enforced; the PR 6 dedup pattern)."""
    assert timeline_lib.load_run_events is events_lib.load_run_events
    path = os.path.join(
        REPO, "distributed_training_pytorch_tpu", "telemetry", "timeline.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    offenders = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None
            )
            if name in ("loads", "read_events", "EventFollower"):
                offenders.append((name, node.lineno))
    assert not offenders, (
        f"timeline.py grew a private event parser at {offenders} — use "
        "telemetry.events.load_run_events/EventFollower (ISSUE 15)")


# ---------------------------------------------------------------------------
# Doctor: the incremental fold IS the batch path.


_HAND_LOG = [
    {"event": "run_start", "t_mono": 0.0, "_line": 1,
     "goodput_seconds": {"productive_step": 0.0}},
    {"event": "compile", "t_mono": 1.0, "epoch": 2, "executables": 1, "_line": 2},
    {"event": "anomaly", "t_mono": 2.0, "kind": "loss_spike", "value": 9.0,
     "_line": 3},
    {"event": "anomaly", "t_mono": 2.5, "kind": "straggler", "value": 2.0,
     "_line": 4},
    {"event": "window", "t_mono": 3.0, "steps": 4, "step_ms": 10.0,
     "straggler_ratio": 2.2, "_line": 5},
    {"event": "hung_step", "t_mono": 4.0, "timeout_s": 5.0, "_line": 6},
    {"event": "profile_capture", "t_mono": 5.0,
     "categories": {"collective": 0.4, "idle": 0.6}, "_line": 7},
    {"event": "run_end", "t_mono": 9.0, "_line": 8,
     "goodput_seconds": {"productive_step": 5.0, "data_wait": 3.0,
                         "checkpoint": 1.0, "compile": 4.0}},
]


def test_update_signals_matches_extract_signals_byte_identical():
    batch = doctor_lib.diagnose([dict(r) for r in _HAND_LOG])
    sig = doctor_lib.Signals()
    for rec in _HAND_LOG:
        doctor_lib.update_signals(sig, dict(rec))
    streaming = doctor_lib.diagnose(sig)
    assert (
        json.dumps(streaming.to_dict(), sort_keys=True)
        == json.dumps(batch.to_dict(), sort_keys=True)
    )
    # and the evidence (line citations included) folded identically
    assert streaming.signals.evidence == batch.signals.evidence


def test_verdict_vocabulary_includes_liveness_kinds():
    assert "stale_heartbeat" in doctor_lib.VERDICTS
    assert "dead" in doctor_lib.VERDICTS
    # the offline rules never produce them: scalar projections stay 0.0
    scores = doctor_lib.scalar_fields(doctor_lib.Signals(
        goodput_seconds={"productive_step": 5.0}))
    assert scores["stale_heartbeat"] == 0.0 and scores["dead"] == 0.0


# ---------------------------------------------------------------------------
# Monitor liveness (fake clock over synthetic logs).


def _mk_run(tmp_path, lines, name="run"):
    run = tmp_path / name
    (run / "telemetry").mkdir(parents=True)
    _write_lines(str(run / "telemetry" / "events.jsonl"), lines)
    return str(run)


def test_monitor_attaches_before_run_dir_exists(tmp_path):
    """Deploy-the-monitor-first: a RunMonitor constructed before the
    trainer has created the run directory must still resolve the log's
    eventual location (an isdir-based resolution would freeze the bare
    dir path and report 'waiting' forever)."""
    base = time.time()
    run = str(tmp_path / "not_yet")  # does not exist at construction
    mon = RunMonitor(run, AlertConfig(stale_after_s=60.0),
                     clock=lambda: base + 1.0)
    assert mon.poll().status == "waiting"
    os.makedirs(os.path.join(run, "telemetry"))
    _append(os.path.join(run, "telemetry", "events.jsonl"),
            _rec("run_start", t_wall=base, t_mono=0.0))
    assert mon.poll().status == "training"


def test_watchdog_fire_does_not_reset_patrol_progress():
    """A fire re-arms the escalation window (_last_pat) but must NOT
    claim progress: patrol heartbeats after a SIGTERM recovery attempt
    still report the hang, or the monitor would read a wedged run as
    'training' for the whole escalation window."""
    patrols = []
    # max_fires=2 = the trainer's config: the patrol thread survives the
    # first (SIGTERM-recovery) fire and keeps pulsing through the
    # escalation window.
    dog = StepWatchdog(timeout=0.1, on_timeout=lambda: None,
                       poll_interval=0.02, max_fires=2,
                       on_patrol=patrols.append)
    dog.start()
    time.sleep(0.4)  # first fire at ~0.1s; never patted
    dog.stop()
    assert dog.fired == 1
    # post-fire patrol figures keep GROWING past the fire point
    assert max(patrols) > 0.25
    assert dog.progress_elapsed > 0.35


def test_monitor_waiting_then_training(tmp_path):
    run = tmp_path / "run"
    (run / "telemetry").mkdir(parents=True)
    base = time.time()
    mon = RunMonitor(str(run), AlertConfig(stale_after_s=5.0),
                     clock=lambda: base + 1.0)
    st = mon.poll()
    assert st.status == "waiting" and st.exit_code == 3
    _append(str(run / "telemetry" / "events.jsonl"),
            _rec("run_start", t_wall=base, t_mono=0.0))
    st = mon.poll()
    assert st.status == "training" and st.verdict == "healthy"
    assert st.exit_code == 0


def test_monitor_stale_heartbeat_from_watchdog_lag(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        # the patrol thread keeps pulsing while the main thread is stuck:
        # fresh record (t_wall base+10), progress 9s before it
        _rec("heartbeat", t_wall=base + 10.0, t_mono=10.0, source="watchdog",
             since_progress_s=9.0),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=5.0, dead_after_s=60.0),
                     clock=lambda: base + 11.0)
    st = mon.poll()
    assert st.status == "stale_heartbeat" and st.verdict == "stale_heartbeat"
    assert st.exit_code == 1
    assert st.progress_age_s == pytest.approx(10.0, abs=1.0)
    assert any(a["rule"] == "stale_heartbeat" for a in st.alerts)


def test_monitor_loop_heartbeat_is_progress(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        _rec("heartbeat", t_wall=base + 10.0, t_mono=10.0, source="loop",
             epoch=0, step_in_epoch=8, units=8, step_ms=3.0),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=5.0, dead_after_s=60.0),
                     clock=lambda: base + 11.0)
    st = mon.poll()
    assert st.status == "training"
    assert st.headline["units"] == 8 and st.headline["step_ms"] == 3.0


def test_monitor_dead_on_silence_and_drains_torn_tail(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        # a SIGKILL'd writer's torn tail: parsed once the run is declared
        # dead (no more bytes are coming)
        '{"event": "window", "t_wall": %r, "t_mono": 5.0, "steps": 4, '
        '"step_ms": 2.0}' % (base + 5.0),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=5.0, dead_after_s=30.0),
                     clock=lambda: base + 100.0)
    st = mon.poll()
    assert st.status == "dead" and st.verdict == "dead" and st.exit_code == 2
    assert any(a["rule"] == "dead" for a in st.alerts)
    # the tail window record was ingested on the final drain
    assert st.headline.get("step_ms") == 2.0


def test_monitor_finished_is_not_dead(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0,
             goodput_seconds={"productive_step": 0.0}),
        _rec("run_end", t_wall=base + 5.0, t_mono=5.0,
             goodput_seconds={"productive_step": 9.0, "data_wait": 0.1}),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=5.0, dead_after_s=30.0),
                     clock=lambda: base + 10_000.0)
    st = mon.poll()
    assert st.status == "finished" and st.verdict == "healthy"
    assert st.exit_code == 0


def test_monitor_resumed_attempt_reopens_the_run(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        _rec("run_end", t_wall=base + 5.0, t_mono=5.0),
        _rec("run_start", t_wall=base + 8.0, t_mono=0.5),  # append-across-restarts
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=60.0), clock=lambda: base + 9.0)
    assert mon.poll().status == "training"


def test_monitor_resets_state_on_log_truncation(tmp_path):
    """A fresh attempt truncating the log must rebuild the monitor's
    accumulated signals — folding the re-read records onto the old run's
    Signals would double-count and weld two runs' verdicts together."""
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        _rec("anomaly", t_wall=base + 1.0, t_mono=1.0, kind="loss_spike",
             value=9.0),
        _rec("hung_step", t_wall=base + 2.0, t_mono=2.0, timeout_s=5.0),
    ])
    path = os.path.join(run, "telemetry", "events.jsonl")
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0),
                     clock=lambda: base + 3.0)
    st = mon.poll()
    assert st.verdict == "straggler"  # hung_step from attempt 1
    assert "anomaly:loss_spike" in st.active_alerts
    # attempt 2 rewrites the log, smaller: clean run, nothing carried over
    _write_lines(path, [_rec("run_start", t_wall=base + 4.0, t_mono=0.0)])
    st = mon.poll()
    assert st.status == "training" and st.verdict == "healthy"
    assert mon.signals.anomaly_counts == {} and mon.signals.hung_steps == 0
    assert st.active_alerts == () and st.exit_code == 0


def test_monitor_attempt_change_resets_and_rebases(tmp_path):
    """ISSUE 16 die-and-restart-in-place: a controller-restarted run
    APPENDS a new attempt to the same events.jsonl. The in-band attempt
    id must (a) walk the fleet-table state dead -> training -> healthy,
    (b) drop the dead attempt's accumulated signals (no welded hung/
    anomaly counters), and (c) rebase goodput at the restored cumulative
    snapshot the new run_start carries, so fraction verdicts describe
    THIS attempt — not the diseased history the restart just cured."""
    base = time.time()
    clock = {"now": base + 3.0}
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0, attempt=1, epoch=0),
        _rec("hung_step", t_wall=base + 1.0, t_mono=1.0, timeout_s=5.0),
        # attempt 1 accrued 80% data_wait before it died
        _rec("epoch_end", t_wall=base + 2.0, t_mono=2.0, epoch=0,
             goodput_seconds={"productive_step": 1.0, "data_wait": 4.0}),
    ])
    path = os.path.join(run, "telemetry", "events.jsonl")
    mon = RunMonitor(run, AlertConfig(stale_after_s=5.0),
                     clock=lambda: clock["now"])
    st = mon.poll()
    assert st.attempt == 1 and st.verdict == "data_bound"
    assert mon.signals.hung_steps == 1  # the hang is on attempt 1's ledger
    assert "data_bound" in st.active_alerts
    # silence past 3x stale ceiling: the attempt reads dead
    clock["now"] = base + 40.0
    assert mon.poll().status == "dead"
    # the controller respawned: attempt 2 appends, carrying the restored
    # cumulative goodput snapshot (trainer restores BEFORE run_start)
    _append(path, _rec("run_start", t_wall=base + 41.0, t_mono=0.0,
                       attempt=2, epoch=1,
                       goodput_seconds={"productive_step": 1.0,
                                        "data_wait": 4.0}))
    _append(path, _rec("epoch_end", t_wall=base + 44.0, t_mono=3.0, epoch=1,
                       goodput_seconds={"productive_step": 4.0,
                                        "data_wait": 4.1}))
    clock["now"] = base + 45.0
    st = mon.poll()
    assert st.status == "training" and st.attempt == 2
    # no welded counters: attempt 1's hang is gone, verdict healthy on
    # attempt 2's OWN accrual (3.0 productive vs 0.1 data_wait), even
    # though the welded cumulative would still read data_bound
    assert mon.signals.hung_steps == 0
    assert st.verdict == "healthy" and "data_bound" not in st.active_alerts
    assert st.steady_fractions["data_wait"] == pytest.approx(0.1 / 3.1)


def test_monitor_alert_rearms_across_attempt_change(tmp_path):
    """A fresh attempt's recurrence of a disease must ALERT AGAIN: the
    debounce ledger belongs to the attempt, not the run directory. Two
    attempts over the line = two firings of the same rule."""
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0, attempt=1),
        _goodput_line(base, 1.0, productive_step=1.0, data_wait=1.0),
    ])
    path = os.path.join(run, "telemetry", "events.jsonl")
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0),
                     clock=lambda: base + 2.0)
    st = mon.poll()
    assert [a["rule"] for a in st.alerts] == ["data_bound"]
    assert mon.poll().alerts == []  # debounced while it persists
    _append(path, _rec("run_start", t_wall=base + 3.0, t_mono=0.0, attempt=2,
                       goodput_seconds={"productive_step": 1.0,
                                        "data_wait": 1.0}))
    _append(path, _rec("epoch_end", t_wall=base + 5.0, t_mono=2.0, epoch=0,
                       goodput_seconds={"productive_step": 2.0,
                                        "data_wait": 3.0}))
    st = mon.poll()
    assert st.attempt == 2
    assert [a["rule"] for a in st.alerts] == ["data_bound"]  # re-armed


def test_worst_exit_code_aggregation():
    def st(code):
        class S:
            exit_code = code
        return S()

    assert worst_exit_code([st(0), st(0)]) == 0
    assert worst_exit_code([st(0), st(1)]) == 1
    assert worst_exit_code([st(1), st(2), st(3)]) == 2
    assert worst_exit_code([st(0), st(3)]) == 3
    assert worst_exit_code([]) == 3


# ---------------------------------------------------------------------------
# Alert rules: debounce, re-arm, min-steady guard, JSONL records.


def _goodput_line(base, t, **buckets):
    return _rec("epoch_end", t_wall=base + t, t_mono=t, epoch=0,
                goodput_seconds=buckets)


def test_alert_debounce_fires_once_then_rearms(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [_rec("run_start", t_wall=base, t_mono=0.0)])
    path = os.path.join(run, "telemetry", "events.jsonl")
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0),
                     clock=lambda: base + 1.0)
    # over the ceiling -> ONE alert
    _append(path, _goodput_line(base, 1.0, productive_step=1.0, data_wait=1.0))
    st = mon.poll()
    assert [a["rule"] for a in st.alerts] == ["data_bound"]
    assert "data_bound" in st.active_alerts and st.exit_code == 1
    # still over -> silence (debounced)
    _append(path, _goodput_line(base, 2.0, productive_step=1.5, data_wait=1.4))
    assert mon.poll().alerts == []
    # recovered -> cleared, re-armed
    _append(path, _goodput_line(base, 3.0, productive_step=20.0, data_wait=1.5))
    st = mon.poll()
    assert st.alerts == [] and "data_bound" not in st.active_alerts
    # over again -> a SECOND alert (the rule re-armed on clear)
    _append(path, _goodput_line(base, 4.0, productive_step=21.0, data_wait=9.0))
    assert [a["rule"] for a in mon.poll().alerts] == ["data_bound"]


def test_alert_min_steady_guard(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        # 90% data_wait but only 0.1s of steady wall: honest noise
        _goodput_line(base, 1.0, productive_step=0.01, data_wait=0.09),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0, min_steady_s=1.0),
                     clock=lambda: base + 2.0)
    st = mon.poll()
    assert st.alerts == [] and "data_bound" not in st.active_alerts


def test_anomaly_kind_alert_and_verdict_transition(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        _rec("anomaly", t_wall=base + 1.0, t_mono=1.0, kind="loss_spike",
             value=9.0),
        _rec("window", t_wall=base + 2.0, t_mono=2.0, steps=4, step_ms=10.0,
             straggler_ratio=2.0),
    ])
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0),
                     clock=lambda: base + 3.0)
    st = mon.poll()
    rules = {a["rule"] for a in st.alerts}
    assert "anomaly:loss_spike" in rules
    assert "straggler" in rules  # verdict transition: ratio 2.0 > 1.5
    assert st.verdict == "straggler" and st.exit_code == 1
    # both stay active, neither re-fires
    _append(os.path.join(run, "telemetry", "events.jsonl"),
            _rec("window", t_wall=base + 4.0, t_mono=4.0, steps=4,
                 step_ms=10.0, straggler_ratio=2.1))
    assert mon.poll().alerts == []


def test_monitor_alert_records_written(tmp_path):
    base = time.time()
    run = _mk_run(tmp_path, [
        _rec("run_start", t_wall=base, t_mono=0.0),
        _goodput_line(base, 1.0, productive_step=1.0, data_wait=1.0),
    ])
    alerts_path = str(tmp_path / "alerts.jsonl")
    log = EventLog(alerts_path, process_index=0)
    mon = RunMonitor(run, AlertConfig(stale_after_s=600.0), alert_log=log,
                     clock=lambda: base + 2.0)
    mon.poll()
    log.close()
    recs = load_run_events(alerts_path)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["event"] == "monitor_alert" and rec["rule"] == "data_bound"
    assert rec["run_dir"] == run and rec["status"] == "training"
    assert rec["value"] == pytest.approx(0.5) and rec["threshold"] == 0.2


# ---------------------------------------------------------------------------
# Status exporter.


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\")*\})?"
    r" [-+0-9.eE]+(nan|inf)?$"
)


def _assert_valid_prometheus(text):
    samples = 0
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("#"):
            assert re.match(r"^# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ", line), line
            continue
        assert _PROM_SAMPLE.match(line), f"invalid exposition line: {line!r}"
        samples += 1
    assert samples > 0
    return samples


def test_prometheus_text_renders_scalars_dicts_and_info():
    text = prometheus_text({
        "step_ms": 12.5,
        "epoch": 3,
        "finished": False,
        "verdict": "data_bound",
        "run_dir": "/tmp/x",
        "goodput_fractions": {"productive_step": 0.75, "data_wait": 0.25},
        "anomaly_counts": {"loss_spike": 2},
        "ignored": [1, 2, 3],  # non-numeric leaves are skipped, never a 500
    })
    _assert_valid_prometheus(text)
    assert 'tpu_trainer_goodput_fractions{bucket="data_wait"} 0.25' in text
    assert 'tpu_trainer_anomaly_counts{kind="loss_spike"} 2.0' in text
    assert "tpu_trainer_step_ms 12.5" in text
    assert 'verdict="data_bound"' in text and "tpu_trainer_up 1" in text


def test_status_endpoint_survives_nonfinite_values():
    """A diverged run (loss=NaN) is exactly when /status gets scraped:
    the payload must stay STRICT json (the events._jsonable rule — bare
    NaN tokens are rejected by jq/JSON.parse)."""
    snap = {"loss": float("nan"), "step_ms": float("inf"), "verdict": "healthy"}
    ex = StatusExporter(lambda: snap, 0, host="127.0.0.1")
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{ex.port}/status", timeout=10).read().decode()
    ex.close()
    assert "NaN" not in body and "Infinity" not in body
    parsed = json.loads(body)  # strict: would reject bare NaN
    assert parsed["loss"] == "nan" and parsed["step_ms"] == "inf"


def test_goodput_evidence_row_is_replaced_not_appended():
    """Heartbeats carry a goodput snapshot every pulse: the doctor's
    goodput evidence must hold ONE row (the latest snapshot), not grow by
    one identical row per heartbeat for the length of the run."""
    sig = doctor_lib.Signals()
    for i in range(50):
        doctor_lib.update_signals(sig, {
            "event": "heartbeat", "t_mono": float(i), "_line": i + 1,
            "goodput_seconds": {"productive_step": float(i)},
        })
    assert len(sig.evidence["goodput"]) == 1
    assert sig.evidence["goodput"][0]["line"] == 50  # the latest wins
    assert sig.goodput_seconds == {"productive_step": 49.0}


def test_exporter_serves_concurrent_requests_and_tears_down():
    snap = {"step_ms": 1.5, "verdict": "healthy",
            "goodput_fractions": {"productive_step": 1.0}}
    ex = StatusExporter(lambda: dict(snap), 0, host="127.0.0.1")
    assert ex.enabled and ex.port
    base = f"http://127.0.0.1:{ex.port}"
    errors = []

    def hammer():
        try:
            for _ in range(10):
                body = urllib.request.urlopen(base + "/status", timeout=10).read()
                assert json.loads(body)["step_ms"] == 1.5
                text = urllib.request.urlopen(base + "/metrics", timeout=10).read()
                _assert_valid_prometheus(text.decode())
        except Exception as e:  # noqa: BLE001 — collected for the assert below
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    port = ex.port
    ex.close()
    assert not ex.enabled
    # teardown released the port: a fresh exporter can bind it
    ex2 = StatusExporter(lambda: {}, port, host="127.0.0.1")
    assert ex2.enabled
    ex2.close()


def test_exporter_port_in_use_degrades_to_warning():
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    warned = []
    ex = StatusExporter(lambda: {}, port, host="127.0.0.1", log=warned.append)
    assert not ex.enabled and ex.port is None
    assert warned and "disabled" in warned[0]
    ex.close()  # idempotent on a disabled exporter
    blocker.close()


def test_exporter_unknown_route_404_and_snapshot_failure_500():
    def boom():
        raise RuntimeError("snapshot bug")

    ex = StatusExporter(boom, 0, host="127.0.0.1")
    base = f"http://127.0.0.1:{ex.port}"
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(base + "/metrics", timeout=10)
    assert e.value.code == 500
    ex.close()
    ex2 = StatusExporter(lambda: {}, 0, host="127.0.0.1")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"http://127.0.0.1:{ex2.port}/nope", timeout=10)
    assert e.value.code == 404
    ex2.close()


# ---------------------------------------------------------------------------
# Watchdog patrol hook.


def test_watchdog_on_patrol_reports_elapsed_and_swallows_errors():
    seen = []

    def patrol(elapsed):
        seen.append(elapsed)
        raise RuntimeError("must never wedge the watchdog")

    dog = StepWatchdog(timeout=50.0, on_timeout=lambda: None,
                       poll_interval=0.02, on_patrol=patrol)
    dog.start()
    time.sleep(0.15)
    dog.pat()
    time.sleep(0.1)
    dog.stop()
    assert len(seen) >= 3  # patrolled repeatedly despite the exception
    assert max(seen) >= 0.1  # elapsed grew while unpatted
    assert min(seen) >= 0.0


# ---------------------------------------------------------------------------
# Trainer integration: heartbeats + exporter, historical program untouched.


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


def make_tiny(tmp_path, **kw):
    defaults = dict(
        max_epoch=2,
        batch_size=8,
        have_validate=False,
        save_folder=str(tmp_path / "run"),
        num_workers=0,
        log_every=2,
        chain_steps=2,
        async_checkpoint=False,
        progress=False,
        logger=_Quiet(),
    )
    defaults.update(kw)
    return TinyTrainer(**defaults)


@pytest.fixture(scope="module")
def hb_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hb_run")
    trainer = make_tiny(tmp, telemetry=Telemetry(heartbeat_every_s=1e-4))
    trainer.train()
    return trainer, load_run_events(trainer.save_folder)


def test_heartbeats_ride_the_log_every_syncs(hb_run):
    trainer, events = hb_run
    hbs = [r for r in events if r["event"] == "heartbeat"]
    assert hbs, "no heartbeat records in a heartbeat-on run"
    assert {h["source"] for h in hbs} == {"loop"}  # no watchdog armed here
    units = [h["units"] for h in hbs]
    assert units == sorted(units)  # progress is monotone
    last = hbs[-1]
    assert set(last["goodput_seconds"]) == set(doctor_lib.BUCKETS)
    assert last["step_ms"] > 0 and last["epoch"] == trainer.max_epoch - 1


def test_heartbeat_off_removes_records(tmp_path):
    trainer = make_tiny(tmp_path, telemetry=Telemetry(heartbeat_every_s=0.0))
    trainer.train()
    events = load_run_events(trainer.save_folder)
    assert not [r for r in events if r["event"] == "heartbeat"]


def test_monitor_matches_doctor_on_real_run(hb_run):
    """ISSUE 15 acceptance: the streaming monitor's fractions equal the
    post-hoc doctor's to 1e-6 on the same log (they are the same floats),
    and the diagnosis dicts are byte-identical."""
    trainer, events = hb_run
    post = doctor_lib.diagnose(events)
    st = RunMonitor(trainer.save_folder).poll()
    assert st.status == "finished"
    doctor_fr = doctor_lib.steady_fractions(post.signals.goodput_seconds or {})
    for bucket, frac in doctor_fr.items():
        assert abs(st.steady_fractions[bucket] - frac) <= 1e-6
    assert (
        json.dumps(st.diagnosis.to_dict(), sort_keys=True)
        == json.dumps(post.to_dict(), sort_keys=True)
    )


def test_timeline_skips_heartbeat_markers(hb_run):
    trainer, events = hb_run
    trace = timeline_lib.build_timeline(events)
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "heartbeat" not in names  # liveness plumbing, not narrative
    # ...but their goodput snapshots refined the span chain: it still
    # re-derives the meter's fractions exactly
    derived = timeline_lib.span_bucket_seconds(trace)
    want = trainer.goodput.to_state()
    total_d, total_w = sum(derived.values()), sum(want.values())
    for bucket, w in want.items():
        assert abs(
            derived.get(bucket, 0.0) / max(total_d, 1e-12)
            - w / max(total_w, 1e-12)
        ) <= 1e-6


def test_exporter_on_is_historical_program(tmp_path, hb_run):
    """THE parity pillar (ISSUE 15 acceptance): export_port= only READS
    host-side snapshots — params and trace_counts bit-identical with the
    exporter off."""
    on_trainer, _ = hb_run
    off = make_tiny(
        tmp_path,
        telemetry=Telemetry(heartbeat_every_s=1e-4, export_port=0),
    )
    # scrape mid-run through the real HTTP surface (piggybacked on the
    # status-update hook so the request lands while training is live)
    scrapes = {}
    orig = off.run_telemetry._update_status

    def spy(**kw):
        orig(**kw)
        if off.exporter is not None and off.exporter.enabled and not scrapes:
            base = f"http://127.0.0.1:{off.exporter.port}"
            scrapes["status"] = json.loads(
                urllib.request.urlopen(base + "/status", timeout=10).read())
            scrapes["metrics"] = urllib.request.urlopen(
                base + "/metrics", timeout=10).read().decode()

    off.run_telemetry._update_status = spy
    off.train()
    assert scrapes, "the exporter never served during the run"
    assert scrapes["status"]["phase"] == "training"
    assert scrapes["status"]["verdict"] == "healthy"
    _assert_valid_prometheus(scrapes["metrics"])
    assert off.exporter is None  # torn down with the run
    assert dict(off.engine.trace_counts) == dict(on_trainer.engine.trace_counts)
    for a, b in zip(
        jax.tree.leaves(off.state.params),
        jax.tree.leaves(on_trainer.state.params),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exporter_port_taken_never_kills_training(tmp_path):
    blocker = socket.socket()
    blocker.bind(("0.0.0.0", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    trainer = make_tiny(
        tmp_path, telemetry=Telemetry(heartbeat_every_s=0.0, export_port=port)
    )
    trainer.train()  # completes despite the bind failure
    blocker.close()
    assert trainer.exporter is None
    events = load_run_events(trainer.save_folder)
    assert any(r["event"] == "run_end" for r in events)
