"""Telemetry subsystem tests (ISSUE 4): event log, goodput accounting,
on-device train-health stats, MFU fields, anomaly detectors, and the
trainer integration's acceptance pillars:

* on-device stats add ZERO extra host syncs and ZERO retraces —
  ``TrainEngine.trace_counts`` identical with telemetry on/off — and never
  perturb the update arithmetic (params bit-exact with a stats-off run);
* chained windows with stats enabled run the arithmetic of single-step runs
  (params and per-step stats within ``test_engine.CHAINED_VS_SINGLE_ULPS``);
* goodput bucket fractions sum to 1, and the cumulative counters survive a
  SIGTERM-kill -> resume cycle bit-identically (the test_fault pattern).

Cost note: trainer tests use a tiny Dense net (seconds of CPU compile, the
test_precision MiniTrainer pattern), never the toy VGG.
"""

import json
import math
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from distributed_training_pytorch_tpu.checkpoint import LAST
from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.fault import FaultPlan
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.telemetry import (
    AnomalyDetector,
    AnomalyError,
    BUCKETS,
    EventLog,
    GoodputMeter,
    Telemetry,
    device_peak_flops,
    mfu_value,
    read_events,
    resolve_telemetry,
    window_report,
)
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from distributed_training_pytorch_tpu.utils.tensorboard import MetricsWriter

from test_engine import (
    CHAINED_VS_SINGLE_ULPS,
    TinyMLP,
    assert_trees_within_ulps,
    criterion,
    synthetic_batch,
)


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# GoodputMeter: exhaustive partition + checkpoint round trip.


def test_goodput_partition_sums_to_one():
    m = GoodputMeter()
    m.start()
    for bucket in ("data_wait", "compile", "productive_step", "checkpoint"):
        m.tick(bucket)
    m.stop()  # trailing interval -> other
    fractions = m.fractions()
    assert set(fractions) == set(BUCKETS)
    assert math.isclose(sum(fractions.values()), 1.0, abs_tol=1e-9)
    assert m.total() == sum(m.buckets.values())


def test_goodput_first_tick_starts_clock_without_attribution():
    m = GoodputMeter()
    assert m.tick("data_wait") == 0.0  # starting tick attributes nothing
    assert m.total() == 0.0
    assert m.tick("productive_step") >= 0.0  # second tick attributes


def test_goodput_rejects_unknown_bucket():
    m = GoodputMeter()
    with pytest.raises(KeyError, match="unknown goodput bucket"):
        m.tick("not_a_bucket")
    with pytest.raises(KeyError, match="unknown goodput bucket"):
        m.account("typo", 1.0)


def test_goodput_state_round_trips_bit_identically_through_json():
    m = GoodputMeter()
    m.account("productive_step", 1.2345678901234567)
    m.account("compile", 0.1)
    m.account("other", 3.3333333333333335e-3)
    state = m.to_state()
    # The checkpoint path: meta json write -> read (json round-trips floats
    # exactly in Python).
    restored = GoodputMeter(json.loads(json.dumps(state)))
    for bucket in BUCKETS:
        assert restored.buckets[bucket] == m.buckets[bucket]  # bit-identical


def test_goodput_unknown_saved_bucket_folds_into_other():
    m = GoodputMeter({"productive_step": 1.0, "renamed_legacy_bucket": 2.0})
    assert m.buckets["productive_step"] == 1.0
    assert m.buckets["other"] == 2.0
    assert math.isclose(sum(m.fractions().values()), 1.0, abs_tol=1e-9)


# ---------------------------------------------------------------------------
# EventLog: JSONL schema, ordering, no-op contract.


def test_event_log_jsonl_well_formed(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("run_start", epoch=0, devices=8)
    log.emit("window", step_ms=1.5, mfu=np.float32(0.42))  # numpy scalar coerces
    log.emit("run_end", weird=object())  # non-serializable -> repr, never raises
    log.close()
    events = list(read_events(path))
    assert [e["event"] for e in events] == ["run_start", "window", "run_end"]
    for e in events:
        for field in ("event", "t_wall", "t_mono", "process", "host", "pid"):
            assert field in e
    mono = [e["t_mono"] for e in events]
    assert mono == sorted(mono)
    assert events[1]["mfu"] == pytest.approx(0.42)
    assert isinstance(events[2]["weird"], str)


def test_event_log_nonfinite_values_stay_strict_json(tmp_path):
    """json.dumps would emit bare NaN/Infinity (invalid strict JSON, rejected
    by jq / JSON.parse); non-finite payload values are preserved as strings."""
    path = str(tmp_path / "e.jsonl")
    log = EventLog(path)
    log.emit("anomaly", value=float("nan"), norm=np.float32("inf"))
    log.close()
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    event = next(iter(read_events(path)))
    assert event["value"] == "nan" and event["norm"] == "inf"


def test_event_log_appends_across_reopen(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("run_start")
    log.close()
    log.emit("run_start")  # a re-entered train() lazily reopens in append mode
    log.close()
    assert [e["event"] for e in read_events(path)] == ["run_start", "run_start"]


def test_event_log_disabled_paths(tmp_path):
    assert EventLog(None).emit("x") is None  # no path
    off = EventLog(str(tmp_path / "e.jsonl"), process_index=1)  # not rank 0
    assert not off.enabled and off.emit("x") is None
    assert not os.path.exists(tmp_path / "e.jsonl")


def test_read_events_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"event": "ok"}\nnot json\n')
    with pytest.raises(ValueError, match="malformed"):
        list(read_events(str(p)))
    # strict=False (post-crash audit): skip-with-warning, keep the stream
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        events = list(read_events(str(p), strict=False))
    assert [e["event"] for e in events] == ["ok"]
    assert any("malformed" in str(w.message) for w in caught)


def test_event_log_repairs_torn_last_line(tmp_path):
    """A hard kill mid-write leaves a partial line; the resumed run's reopen
    must newline-terminate it so records never merge."""
    path = str(tmp_path / "e.jsonl")
    log = EventLog(path)
    log.emit("run_start")
    log.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"event": "torn-by-sigk')  # no trailing newline
    resumed = EventLog(path)
    resumed.emit("run_start")
    resumed.close()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        events = list(read_events(path, strict=False))
    assert [e["event"] for e in events] == ["run_start", "run_start"]


# ---------------------------------------------------------------------------
# AnomalyDetector: spikes, warmup, non-finite handling.


def test_anomaly_loss_spike_after_warmup():
    det = AnomalyDetector(warmup=3, loss_spike=3.0)
    for step in range(5):
        assert det.observe(step, loss=1.0) == []
    found = det.observe(5, loss=10.0)
    assert [a.kind for a in found] == ["loss_spike"]
    assert found[0].value == 10.0 and found[0].baseline == pytest.approx(1.0)
    assert det.total_fired == 1


def test_anomaly_warmup_suppresses_early_spikes():
    det = AnomalyDetector(warmup=5, loss_spike=3.0)
    # A wild but early value must not fire (init transients are normal).
    assert det.observe(0, loss=1.0) == []
    assert det.observe(1, loss=50.0) == []


def test_anomaly_grad_explosion_and_step_time_regression():
    det = AnomalyDetector(warmup=2, grad_explosion=10.0, step_time_regression=2.5)
    for step in range(4):
        assert det.observe(step, grad_norm=0.5, step_time=0.1) == []
    found = det.observe(4, grad_norm=50.0, step_time=1.0)
    assert sorted(a.kind for a in found) == ["grad_explosion", "step_time_regression"]


def test_anomaly_nonfinite_fires_and_never_poisons_baseline():
    det = AnomalyDetector(warmup=2, loss_spike=3.0)
    for step in range(3):
        det.observe(step, loss=1.0)
    assert [a.kind for a in det.observe(3, loss=float("nan"))] == ["loss_spike"]
    # baseline survived the NaN: a normal value right after does not fire
    assert det.observe(4, loss=1.0) == []


def test_anomaly_nonfinite_fires_even_with_disabled_factor():
    """factor=None disables the EWMA threshold, NOT non-finite detection."""
    det = AnomalyDetector(loss_spike=None)
    assert det.observe(0, loss=1.0) == []
    assert [a.kind for a in det.observe(1, loss=float("inf"))] == ["loss_spike"]


def test_anomaly_rejects_bad_action():
    with pytest.raises(ValueError, match="action"):
        AnomalyDetector(action="explode")


# ---------------------------------------------------------------------------
# MFU fields.


def test_mfu_value_and_degenerate_cases():
    assert mfu_value(5e11, 1.0, 1e12) == pytest.approx(0.5)
    assert mfu_value(0.0, 1.0, 1e12) is None
    assert mfu_value(1e12, 0.0, 1e12) is None
    assert mfu_value(1e12, 1.0, 0.0) is None
    assert mfu_value(1e12, 1.0, None) is None  # a device with no published peak


def test_device_peak_flops_table(devices):
    """No CPU row, no default: a kind that is not in the table has no peak."""
    assert device_peak_flops(devices[0]) is None  # the CPU
    unknown = type("D", (), {"device_kind": "TPU v99 imaginary"})()
    assert device_peak_flops(unknown) is None
    fake_v5e = type("D", (), {"device_kind": "TPU v5 lite"})()
    assert device_peak_flops(fake_v5e) == 197e12


def test_window_report_fields():
    r = window_report(10, 1.0, flops_per_step=2e11, peak_flops=1e12)
    assert r["steps"] == 10
    assert r["step_ms"] == pytest.approx(100.0)
    assert r["mfu"] == pytest.approx(2.0)  # synthetic numbers, exact ratio
    assert "mfu" not in window_report(10, 1.0, flops_per_step=None, peak_flops=1e12)
    assert "mfu" not in window_report(10, 1.0, flops_per_step=2e11, peak_flops=None)


def test_resolve_telemetry_specs():
    assert resolve_telemetry(None) is None
    assert resolve_telemetry(False) is None
    assert resolve_telemetry("off") is None
    assert isinstance(resolve_telemetry(True), Telemetry)
    assert isinstance(resolve_telemetry("on"), Telemetry)
    t = Telemetry(stats=False)
    assert resolve_telemetry(t) is t
    with pytest.raises(ValueError):
        resolve_telemetry("sideways")
    with pytest.raises(TypeError):
        resolve_telemetry(42)


# ---------------------------------------------------------------------------
# MetricsWriter satellite: one-shot coercion + non-finite tolerance.


class _FakeBackend:
    def __init__(self):
        self.scalars = []
        self.flushes = 0

    def add_scalar(self, tag, value, step):
        assert isinstance(value, float) and isinstance(step, int)
        self.scalars.append((tag, value, step))

    def flush(self):
        self.flushes += 1


def test_metrics_writer_coerces_scalars_and_tolerates_nonfinite():
    writer = MetricsWriter(None)
    writer._writer = _FakeBackend()  # bypass tensorboardX presence
    writer.write(
        np.int64(7),
        {
            "plain": 1.5,
            "numpy": np.float32(2.5),
            "zero_d": np.asarray(3.5),
            "jax": jnp.asarray(4.5),
            "nan": float("nan"),          # tolerated: skipped, no crash
            "inf": np.float32("inf"),     # tolerated: skipped, no crash
            "vector": np.zeros(3),        # non-scalar: skipped
            "string": "not a number",     # non-numeric: skipped
        },
        prefix="t",
    )
    backend = writer._writer
    assert [(t, v) for t, v, _ in backend.scalars] == [
        ("t/plain", 1.5),
        ("t/numpy", 2.5),
        ("t/zero_d", 3.5),
        ("t/jax", 4.5),
    ]
    assert all(s == 7 for _, _, s in backend.scalars)
    assert backend.flushes == 1


# ---------------------------------------------------------------------------
# Engine: on-device stats — presence, bit-exactness, zero retraces.


def make_engine(stats=False, nan_guard=False):
    mesh = mesh_lib.create_mesh()
    model = TinyMLP()
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh,
        stats=stats,
        nan_guard=nan_guard,
    )
    state = engine.init_state(
        jax.random.key(0), lambda rng: model.init(rng, jnp.zeros((1, 4, 4, 3)))
    )
    return engine, state


def test_stats_metrics_present_and_sane(devices):
    engine, state = make_engine(stats=True)
    state, m = engine.train_step(state, engine.shard_batch(synthetic_batch(16, seed=0)))
    m = jax.device_get(m)
    assert float(m["grad_norm"]) > 0
    assert float(m["param_norm"]) > 0
    assert float(m["update_ratio"]) > 0
    assert float(m["nonfinite"]) == 0.0


def test_stats_flag_nonfinite_on_poisoned_batch(devices):
    engine, state = make_engine(stats=True)
    batch = synthetic_batch(16, seed=1)
    batch = dict(batch, image=np.full_like(batch["image"], np.nan))
    state, m = engine.train_step(state, engine.shard_batch(batch))
    assert float(m["nonfinite"]) == 1.0
    assert not np.isfinite(float(m["grad_norm"]))


def test_stats_do_not_perturb_training(devices):
    """The norms read the dataflow without feeding back into it: params and
    opt_state stay BIT-EXACT with a stats-off run on the same stream."""
    eng_off, state_off = make_engine(stats=False)
    eng_on, state_on = make_engine(stats=True)
    for i in range(3):
        b = synthetic_batch(16, seed=10 + i)
        state_off, _ = eng_off.train_step(state_off, eng_off.shard_batch(b))
        state_on, _ = eng_on.train_step(state_on, eng_on.shard_batch(b))
    assert_trees_equal(state_off.params, state_on.params)
    assert_trees_equal(state_off.opt_state, state_on.opt_state)


def test_stats_chained_bit_exact_with_single_step(devices):
    """Chained windows with stats enabled == sequential single steps with
    stats enabled: the same step count and ``nonfinite`` flags, params,
    opt_state and every per-step stat (stacked scan outputs) within
    CHAINED_VS_SINGLE_ULPS."""
    host = [synthetic_batch(16, seed=20 + i) for i in range(4)]
    eng_a, state_a = make_engine(stats=True)
    eng_b, state_b = make_engine(stats=True)
    seq = []
    for hb in host:
        state_a, m = eng_a.train_step(state_a, eng_a.shard_batch(hb))
        seq.append(jax.device_get(m))
    stacked_host = jax.tree.map(lambda *xs: np.stack(xs), *host)
    gb = mesh_lib.global_chain_array_from_host_local(stacked_host, eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 4)
    assert int(state_b.step) == int(state_a.step) == 4
    assert_trees_within_ulps(state_a.params, state_b.params, CHAINED_VS_SINGLE_ULPS)
    assert_trees_within_ulps(state_a.opt_state, state_b.opt_state, CHAINED_VS_SINGLE_ULPS)
    stacked = jax.device_get(stacked)
    for i, m in enumerate(seq):
        np.testing.assert_array_equal(m["nonfinite"], stacked["nonfinite"][i])
        for key in ("grad_norm", "param_norm", "update_ratio", "loss"):
            assert_trees_within_ulps(m[key], stacked[key][i], CHAINED_VS_SINGLE_ULPS)


def test_stats_compose_with_nan_guard(devices):
    """Guard + stats: ONE nonfinite key (the guard's exact per-leaf
    predicate), stats norms alongside, the poisoned update still dropped."""
    engine, state = make_engine(stats=True, nan_guard=True)
    batch = synthetic_batch(16, seed=2)
    poisoned = dict(batch, image=np.full_like(batch["image"], np.nan))
    state, m = engine.train_step(state, engine.shard_batch(poisoned))
    assert float(m["nonfinite"]) == 1.0
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_step_cost_analysis_probe_leaves_trace_counts_alone(devices):
    engine, state = make_engine()
    batch = engine.shard_batch(synthetic_batch(16, seed=3))
    state, _ = engine.train_step(state, batch)
    before = dict(engine.trace_counts)
    cost = engine.step_cost_analysis(state, batch)
    assert float(cost.get("flops", 0.0)) > 0
    assert dict(engine.trace_counts) == before
    # abstract avals work too (what the trainer's probe passes)
    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
    )
    cost2 = engine.step_cost_analysis(state, abstract_batch)
    assert cost2.get("flops") == cost.get("flops")
    assert dict(engine.trace_counts) == before


def test_compile_step_probe_memoized_per_shape(devices):
    """Telemetry's MFU probe and profiling's roofline join share one probe
    compile: same abstract shapes must return the cached executable, a new
    batch shape must compile fresh."""
    engine, state = make_engine()
    batch = engine.shard_batch(synthetic_batch(16, seed=4))
    first = engine.compile_step_probe(state, batch)
    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
    )
    assert engine.compile_step_probe(state, abstract_batch) is first
    assert engine.compile_step_probe(state, batch) is first
    other = engine.shard_batch(synthetic_batch(32, seed=4))
    assert engine.compile_step_probe(state, other) is not first


# ---------------------------------------------------------------------------
# Trainer integration: a tiny Dense trainer (compile cost: seconds).


class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(16)(x)
        x = nn.relu(x)
        return nn.Dense(3)(x)


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
def telemetry_run(tmp_path_factory, mesh):
    """One chained telemetry-on run backing the read-only assertions."""
    tmp = tmp_path_factory.mktemp("telemetry_run")
    trainer = make_tiny(tmp, mesh, telemetry="on")
    trainer.train()
    events = list(
        read_events(os.path.join(trainer.save_folder, "telemetry", "events.jsonl"))
    )
    return trainer, events


def test_trainer_event_log_narrative(telemetry_run):
    trainer, events = telemetry_run
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    for required in ("window", "compile", "epoch_end"):
        assert required in kinds, kinds
    mono = [e["t_mono"] for e in events]
    assert mono == sorted(mono)
    run_end = events[-1]
    assert run_end["preempted"] is False
    assert math.isclose(
        sum(run_end["goodput_fractions"].values()), 1.0, abs_tol=1e-6
    )


def test_trainer_goodput_fractions_sum_to_one(telemetry_run):
    trainer, _ = telemetry_run
    fractions = trainer.goodput.fractions()
    assert math.isclose(sum(fractions.values()), 1.0, abs_tol=1e-9)
    assert trainer.goodput.buckets["compile"] > 0
    assert trainer.goodput.buckets["productive_step"] > 0
    assert trainer.goodput.buckets["data_wait"] > 0


def test_trainer_mfu_probe_ran_once(telemetry_run):
    trainer, events = telemetry_run
    assert trainer._flops_per_step and trainer._flops_per_step > 0
    probes = [e for e in events if e["event"] == "compile" and e.get("kind") == "mfu_probe"]
    assert len(probes) == 1
    # The count is the WHOLE mesh's work: cost_analysis() of the partitioned
    # program is ONE device's share (less than the same step lowered for a
    # one-device mesh), and the utilisation denominator is the mesh's peak, so
    # the trainer reports share x devices — an upper bound on the one-device
    # figure, because every device repeats the replicated-parameter update.
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state)
    share = trainer.engine.step_cost_analysis(abstract, trainer._abstract_batch)["flops"]
    solo = trainer.engine.with_mesh(mesh_lib.create_mesh(devices=jax.devices()[:1]))
    solo_flops = solo.step_cost_analysis(abstract, trainer._abstract_batch)["flops"]
    assert trainer.mesh.devices.size == 8
    assert trainer._flops_per_step == pytest.approx(share * 8)
    assert share < solo_flops <= trainer._flops_per_step
    # The CPU has no published peak, so no window claims a utilisation (the
    # ratio itself is covered by test_window_report_fields; on a TPU the
    # field's presence is asserted by chip_smoke.py).
    assert trainer._peak_flops is None
    assert not [e for e in events if e["event"] == "window" and "mfu" in e]


def test_trainer_epoch_metrics_carry_health_stats(telemetry_run):
    trainer, events = telemetry_run
    epoch_end = [e for e in events if e["event"] == "epoch_end"][-1]
    for key in ("grad_norm", "step_ms"):
        assert key in epoch_end and np.isfinite(epoch_end[key])
    assert epoch_end["nonfinite"] == 0.0


def test_trainer_telemetry_zero_retrace_and_bit_exact(tmp_path, mesh, telemetry_run):
    """THE acceptance test: trace_counts (and so per-shape compiles and the
    per-step dispatch structure) identical with telemetry on/off, and final
    params bit-exact — telemetry observes the run, it does not alter it."""
    on, _ = telemetry_run
    off = make_tiny(tmp_path, mesh, telemetry=None)
    off.train()
    assert dict(off.engine.trace_counts) == dict(on.engine.trace_counts)
    assert_trees_equal(off.state.params, on.state.params)
    assert_trees_equal(off.state.opt_state, on.state.opt_state)
    # off = the historical program: no events file, no meter
    assert off.goodput is None and not off.events.enabled
    assert not os.path.exists(os.path.join(off.save_folder, "telemetry"))


def test_goodput_counters_survive_sigterm_resume_bit_identically(tmp_path, mesh):
    """Kill/resume acceptance (test_fault pattern): an injected real SIGTERM
    interrupts epoch 1; the preemption save embeds the goodput counters in
    checkpoint meta; the resumed trainer re-seeds them BIT-IDENTICALLY and
    books the restore as restart_rollback."""
    kw = dict(telemetry="on", chain_steps=1, log_every=0)
    plan = FaultPlan().add("sigterm", epoch=1, step=2)
    interrupted = make_tiny(tmp_path, mesh, fault_plan=plan, **kw)
    interrupted.train()
    assert interrupted._preempted and interrupted.checkpoints.exists(LAST)
    meta = interrupted.checkpoints.read_meta(LAST)
    saved = meta["telemetry"]["goodput"]
    assert set(saved) == set(BUCKETS)

    resumed = make_tiny(
        tmp_path, mesh, snapshot_path=interrupted.checkpoints.path(LAST), **kw
    )
    for bucket, value in saved.items():
        if bucket == "restart_rollback":
            # the restore itself is rollback overhead, booked on top
            assert resumed.goodput.buckets[bucket] > value
        else:
            assert resumed.goodput.buckets[bucket] == value  # bit-identical
    resumed.train()
    # counters only grew; the partition property held through the carry
    assert resumed.goodput.total() > sum(saved.values())
    assert math.isclose(sum(resumed.goodput.fractions().values()), 1.0, abs_tol=1e-9)
    # the run's flight record shows the whole story
    events = [
        e["event"]
        for e in read_events(
            os.path.join(resumed.save_folder, "telemetry", "events.jsonl")
        )
    ]
    for required in ("fault_injection", "preemption", "checkpoint_save",
                     "checkpoint_restore"):
        assert required in events, events


def test_anomaly_raise_action_aborts_training(tmp_path, mesh):
    """anomaly='raise' + a mid-run NaN loss (no nan guard): the log_every
    sync sees the raw per-step loss (epoch means exclude flagged steps) and
    the detector turns the non-finite value into AnomalyError."""
    plan = FaultPlan().add("nan_loss", epoch=1, step=1)
    trainer = make_tiny(
        tmp_path,
        mesh,
        fault_plan=plan,
        chain_steps=1,
        log_every=2,
        # The wall-clock detectors are off: on a loaded CPU host a timing
        # anomaly can fire (and raise) before the injected NaN does.
        telemetry=Telemetry(anomaly=AnomalyDetector(
            action="raise", warmup=0, straggler=None, step_time_regression=None,
        )),
    )
    with pytest.raises(AnomalyError, match="loss_spike"):
        trainer.train()
