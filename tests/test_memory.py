"""Memory observability subsystem tests (ISSUE 8): per-buffer attribution,
OOM preflight, live-memory telemetry, and the trainer integration's
acceptance pillars:

* attribution is EXACT and exhaustive — hand-computed on synthetic stats,
  buffer-class fractions sum to 1 on the real single-step AND chained
  programs, and the predicted peak equals the number re-derived from
  ``compiled.memory_analysis()`` (self-parity);
* preflight bisection is boundary-exact: the recommended batch's predicted
  peak fits, the next shard-multiple's does not;
* ``Trainer(preflight=None)`` reproduces the historical program —
  trace_counts identical and params bit-exact with a preflight-on run
  (the telemetry/profiling parity convention) — and a predicted OOM fails
  BEFORE anything is dispatched (trace_counts empty);
* the memory-growth detector fires on an injected leak and stays quiet on
  a flat run; statless backends (CPU) degrade to absent fields everywhere.

Cost note: every attribution/preflight check lowers the TinyMLP engine on
abstract avals (sub-second CPU compiles); nothing here executes a step
except the trainer parity tests (the test_telemetry TinyTrainer).
"""

import os

import jax
import numpy as np
import pytest

from distributed_training_pytorch_tpu.memory import (
    BUFFER_CLASSES,
    Preflight,
    PreflightOOMError,
    analyze_step_memory,
    attribute_memory,
    device_memory_stats,
    is_oom_error,
    live_memory_fields,
    memory_skew,
    resolve_preflight,
    run_preflight,
    top_buffers_from_hlo,
)
from distributed_training_pytorch_tpu.memory.analysis import stack_chain_batch
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.telemetry import AnomalyDetector, read_events

from test_engine import make_engine, synthetic_batch
from test_telemetry import assert_trees_equal, make_tiny


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


@pytest.fixture(scope="module")
def engine_state():
    return make_engine()


# ---------------------------------------------------------------------------
# Attribution core: pure arithmetic, hand-checkable.


def test_attribute_memory_hand_computed():
    """Exact partition on synthetic stats: arg 1000 pro-rated 500/300/200
    over params/opt/batch, grads = min(temp, grad_bytes) = 400, activations
    = remaining temp 200 + unaliased out 50, executable = code 30. Peak =
    1000 + 150 - 100 + 600 + 30 = 1680 and the classes sum to it exactly."""
    stats = {
        "argument_size_in_bytes": 1000,
        "output_size_in_bytes": 150,
        "alias_size_in_bytes": 100,
        "temp_size_in_bytes": 600,
        "generated_code_size_in_bytes": 30,
    }
    profile = attribute_memory(
        stats,
        {"params": 500.0, "optimizer_state": 300.0, "input_batch": 200.0},
        grad_bytes=400.0,
    )
    assert profile.peak_bytes == 1680
    assert profile.bytes_by_class == {
        "params": 500.0,
        "optimizer_state": 300.0,
        "input_batch": 200.0,
        "gradients": 400.0,
        "activations": 200.0 + 50.0,
        "executable": 30.0,
    }
    assert sum(profile.bytes_by_class.values()) == profile.peak_bytes
    assert abs(sum(profile.fractions().values()) - 1.0) < 1e-12


def test_attribute_memory_pro_rata_absorbs_padding():
    """XLA-reported argument bytes (padding included) are what gets
    partitioned — the class split scales to the reported total, not the
    aval sum (600 reported vs 300 aval: every class doubles)."""
    stats = {
        "argument_size_in_bytes": 600,
        "output_size_in_bytes": 0,
        "alias_size_in_bytes": 0,
        "temp_size_in_bytes": 0,
        "generated_code_size_in_bytes": 0,
    }
    profile = attribute_memory(
        stats, {"params": 100.0, "optimizer_state": 100.0, "input_batch": 100.0}, 0.0
    )
    assert profile.bytes_by_class["params"] == 200.0
    assert sum(profile.bytes_by_class.values()) == 600


def test_attribute_memory_no_classable_inputs_spills_to_activations():
    stats = {
        "argument_size_in_bytes": 64,
        "output_size_in_bytes": 0,
        "alias_size_in_bytes": 0,
        "temp_size_in_bytes": 0,
        "generated_code_size_in_bytes": 0,
    }
    profile = attribute_memory(stats, {}, 0.0)
    assert profile.bytes_by_class["activations"] == 64.0
    assert profile.peak_bytes == 64


def test_attribute_memory_grads_capped_by_temp():
    """XLA may alias/fold gradient buffers away: the gradients class never
    exceeds the temp space that actually exists."""
    stats = {
        "argument_size_in_bytes": 0,
        "output_size_in_bytes": 0,
        "alias_size_in_bytes": 0,
        "temp_size_in_bytes": 100,
        "generated_code_size_in_bytes": 0,
    }
    profile = attribute_memory(stats, {}, grad_bytes=1_000_000.0)
    assert profile.bytes_by_class["gradients"] == 100.0
    assert profile.bytes_by_class["activations"] == 0.0


def test_top_buffers_from_hlo_exact_rows():
    hlo = """
ENTRY %main (p0: f32[8,16]) -> f32[8] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %big = bf16[64,64]{1,0} fusion(f32[8,16]{1,0} %p0), metadata={op_name="jit(step)/dot"}
  ROOT %out = f32[8]{0} reduce(f32[8,16]{1,0} %p0)
}
"""
    rows = top_buffers_from_hlo(hlo, top_k=2)
    assert rows[0]["name"] == "big" and rows[0]["op"] == "fusion"
    assert rows[0]["bytes"] == 64 * 64 * 2  # bf16
    assert rows[0]["op_name"] == "jit(step)/dot"
    assert rows[1] == {
        "name": "p0", "op": "parameter", "shape": [8, 16], "dtype": "f32",
        "bytes": 8 * 16 * 4, "op_name": "",
    }
    assert top_buffers_from_hlo(hlo, top_k=0) == []


# ---------------------------------------------------------------------------
# Real programs: exhaustive fractions + self-parity with memory_analysis.


def _independent_peak(engine, state, batch, chain_length=None):
    """Re-derive the peak straight from the probe's CompiledMemoryStats —
    stdlib arithmetic, independent of memory/analysis.py."""
    probe_batch = stack_chain_batch(batch, chain_length) if chain_length else batch
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(np.shape(x)), np.asarray(x).dtype)
        if not hasattr(x, "dtype") or not hasattr(x, "shape")
        else jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
        probe_batch,
    )
    stats = engine.compile_step_probe(
        state, abstract, donate=True, chain_length=chain_length
    ).memory_analysis()
    return int(
        stats.argument_size_in_bytes
        + stats.output_size_in_bytes
        - stats.alias_size_in_bytes
        + stats.temp_size_in_bytes
        + stats.generated_code_size_in_bytes
    )


def test_fractions_sum_to_one_single_step(devices, engine_state):
    engine, state = engine_state
    profile = analyze_step_memory(engine, state, synthetic_batch(32))
    assert set(profile.bytes_by_class) == set(BUFFER_CLASSES)
    assert all(v >= 0 for v in profile.bytes_by_class.values())
    assert abs(sum(profile.fractions().values()) - 1.0) < 1e-6
    assert profile.peak_bytes > 0
    assert profile.top_buffers and profile.top_buffers[0]["bytes"] > 0


def test_fractions_sum_to_one_chained(devices, engine_state):
    engine, state = engine_state
    batch = synthetic_batch(32)
    single = analyze_step_memory(engine, state, batch)
    chained = analyze_step_memory(engine, state, batch, chain_length=2)
    assert abs(sum(chained.fractions().values()) - 1.0) < 1e-6
    assert chained.chain_length == 2
    # two global batches staged at once: the window program's input-batch
    # class (and so its peak) exceeds the single step's
    assert chained.bytes_by_class["input_batch"] > single.bytes_by_class["input_batch"]
    assert chained.peak_bytes > single.peak_bytes


def test_predicted_peak_self_parity_with_memory_analysis(devices, engine_state):
    """THE tentpole invariant: the preflight's prediction IS XLA's buffer
    assignment, on both real programs."""
    engine, state = engine_state
    batch = synthetic_batch(32)
    for chain_length in (None, 2):
        profile = analyze_step_memory(engine, state, batch, chain_length=chain_length)
        assert profile.peak_bytes == _independent_peak(engine, state, batch, chain_length)


def test_analyze_leaves_trace_counts_alone(devices, engine_state):
    """Attribution rides compile_step_probe: zero trace-count side effects
    (the MFU-probe/profiling convention) — dispatch executables untouched."""
    engine, state = engine_state
    before = dict(engine.trace_counts)
    analyze_step_memory(engine, state, synthetic_batch(32), chain_length=2)
    assert dict(engine.trace_counts) == before


# ---------------------------------------------------------------------------
# Preflight: fit verdicts, bisection boundary, resolution protocol.


def test_preflight_fits_under_huge_capacity(devices, engine_state):
    engine, state = engine_state
    report = run_preflight(
        engine, state, synthetic_batch(32), Preflight(capacity_bytes=1 << 50)
    )
    assert report.fits is True
    assert report.recommended_batch is None and report.recommended_accum is None
    assert report.batch_size == 32
    assert report.predicted_peak_bytes == report.profile.peak_bytes


def test_preflight_bisection_monotonic_boundary(devices, engine_state):
    """The recommendation is boundary-exact: the recommended batch's
    predicted peak fits the usable budget, the next shard-multiple's does
    not (monotonicity of peak in batch size, bisected)."""
    engine, state = engine_state
    batch = synthetic_batch(32)
    shard = 8  # data-axis extent of the 8-device mesh
    p_small = analyze_step_memory(
        engine, state, synthetic_batch(shard), top_k=0
    ).peak_bytes
    p_full = analyze_step_memory(engine, state, batch, top_k=0).peak_bytes
    assert p_small < p_full
    usable = (p_small + p_full) // 2
    with pytest.raises(PreflightOOMError) as err:
        run_preflight(
            engine, state, batch,
            Preflight(capacity_bytes=usable, headroom=0.0),
        )
    report = err.value.report
    rec = report.recommended_batch
    assert rec is not None and rec % shard == 0 and shard <= rec < 32
    fit_peak = analyze_step_memory(
        engine, state, synthetic_batch(rec), top_k=0
    ).peak_bytes
    next_peak = analyze_step_memory(
        engine, state, synthetic_batch(rec + shard), top_k=0
    ).peak_bytes
    assert fit_peak <= report.usable_bytes < next_peak
    assert report.trials <= Preflight().max_trials
    # the failure message names the recommendation
    assert f"batch {rec}" in str(err.value)


def test_preflight_warn_action_does_not_raise(devices, engine_state):
    engine, state = engine_state
    warnings_seen = []
    report = run_preflight(
        engine, state, synthetic_batch(32),
        Preflight(capacity_bytes=1000, action="warn", recommend=False),
        log=lambda msg, log_type="info": warnings_seen.append((log_type, msg)),
    )
    assert report.fits is False
    assert any(t == "warning" and "predicted OOM" in m for t, m in warnings_seen)


def test_preflight_unknown_capacity_skips_check(devices, engine_state):
    """CPU reports no memory_stats: the fit check is skipped (fits=None),
    the prediction still lands, nothing raises."""
    engine, state = engine_state
    report = run_preflight(engine, state, synthetic_batch(32), Preflight())
    assert report.fits is None and report.capacity_bytes is None
    assert report.predicted_peak_bytes > 0


def test_preflight_degrades_when_backend_has_no_memory_analysis(devices, engine_state):
    """A backend whose compiled programs expose no memory_analysis must not
    kill training through an observability knob: run_preflight warns and
    returns None instead of raising."""
    engine, state = engine_state

    class NoAnalysis:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            if name == "memory_analysis":
                raise AttributeError(name)
            return getattr(self._inner, name)

    real = engine.compile_step_probe
    warnings_seen = []
    try:
        engine.compile_step_probe = lambda *a, **k: NoAnalysis(real(*a, **k))
        report = run_preflight(
            engine, state, synthetic_batch(32), Preflight(capacity_bytes=1),
            log=lambda msg, log_type="info": warnings_seen.append((log_type, msg)),
        )
    finally:
        engine.compile_step_probe = real
    assert report is None
    assert any(t == "warning" and "preflight skipped" in m for t, m in warnings_seen)


def test_preflight_bisection_does_not_grow_probe_cache(devices):
    """Recommendation trials are throwaway compiles: the engine's memoizing
    probe cache must not accumulate one loaded executable per trial shape
    (only the configured shape's probe may land there)."""
    engine, state = make_engine()
    batch = synthetic_batch(32)
    p_small = analyze_step_memory(engine, state, synthetic_batch(8), top_k=0).peak_bytes
    p_full = analyze_step_memory(engine, state, batch, top_k=0).peak_bytes
    cache_before = len(engine._step_probe_cache)
    with pytest.raises(PreflightOOMError) as err:
        run_preflight(
            engine, state, batch,
            Preflight(capacity_bytes=(p_small + p_full) // 2, headroom=0.0),
        )
    assert err.value.report.trials > 0
    assert len(engine._step_probe_cache) == cache_before


def test_resolve_preflight_specs():
    assert resolve_preflight(None) is None
    assert resolve_preflight(False) is None
    assert resolve_preflight("off") is None
    assert isinstance(resolve_preflight(True), Preflight)
    assert isinstance(resolve_preflight("on"), Preflight)
    assert isinstance(resolve_preflight("check"), Preflight)
    config = Preflight(headroom=0.2)
    assert resolve_preflight(config) is config
    with pytest.raises(ValueError):
        resolve_preflight("sideways")
    with pytest.raises(TypeError):
        resolve_preflight(3.14)
    with pytest.raises(ValueError):
        Preflight(action="explode")
    with pytest.raises(ValueError):
        Preflight(headroom=1.5)


def test_engine_with_accum_twin(devices, engine_state):
    engine, state = engine_state
    twin = engine.with_accum(2)
    assert twin is not engine and twin.accum_steps == 2
    assert twin.mesh is engine.mesh and twin.loss_fn is engine.loss_fn
    # the twin's program lowers and analyzes like the original's
    profile = analyze_step_memory(twin, state, synthetic_batch(32), top_k=0)
    assert profile.peak_bytes > 0
    with pytest.raises(ValueError):
        engine.with_accum(0)


# ---------------------------------------------------------------------------
# Sharded avals (ISSUE 10): per-device attribution + the fsdp recommendation.


def _wide_mlp_engine(mesh, fsdp_min_size=256):
    """A param-heavy MLP (one 48x512 kernel dominates) so fsdp sharding
    moves the predicted peak measurably — the capacity window the
    recommendation test sits inside."""
    import optax
    from flax import linen as nn

    from distributed_training_pytorch_tpu.ops import cross_entropy_loss
    from distributed_training_pytorch_tpu.train import (
        TrainEngine,
        make_supervised_loss,
    )

    class WideMLP(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = x.reshape(x.shape[0], -1)
            x = nn.relu(nn.Dense(512)(x))
            return nn.Dense(3)(x)

    model = WideMLP()

    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss}

    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh,
        fsdp_min_size=fsdp_min_size,
    )
    state = engine.init_state(
        jax.random.key(0),
        lambda r: model.init(r, jax.numpy.zeros((1, 4, 4, 3))),
    )
    return engine, state


def test_fsdp_attribution_uses_per_device_shard_bytes(devices):
    """ISSUE 10 satellite acceptance: on an FSDP program the params /
    optimizer classes must be the per-device SHARD bytes (global / extent
    for the sharded leaves), and input_batch the per-device rows — exactly
    what the SPMD executable's memory_analysis() reports — not global aval
    bytes, which would overstate the sharded classes by the extent."""
    from distributed_training_pytorch_tpu.memory.analysis import state_class_bytes

    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.FSDP_AXIS: 4}, devices=devices
    )
    engine, state = _wide_mlp_engine(mesh)
    sharding_tree = engine.state_sharding_tree(state)
    specs = [str(s.spec) for s in jax.tree.leaves(
        sharding_tree, is_leaf=lambda x: hasattr(x, "spec"))]
    assert any("fsdp" in s for s in specs), specs

    batch = synthetic_batch(32)
    profile = analyze_step_memory(engine, state, batch, top_k=0)
    # Exact hand-derivation: per-device class bytes through the same shard
    # arithmetic, pro-rated over XLA's reported argument total.
    per_device = state_class_bytes(state, sharding_tree)
    global_classes = state_class_bytes(state)
    # the 48x512 kernel (and its momentum) shard 4-way: per-device params
    # land well under global.
    assert per_device["params"] < 0.5 * global_classes["params"]
    assert per_device["optimizer_state"] < 0.5 * global_classes["optimizer_state"]
    batch_sharding = mesh_lib.batch_sharding(mesh)
    from distributed_training_pytorch_tpu.memory.analysis import batch_class_bytes

    per_device_batch = batch_class_bytes(
        jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype), batch
        ),
        batch_sharding,
    )
    assert per_device_batch == batch_class_bytes(batch) / 8  # data x fsdp = 8
    arg = float(profile.stats["argument_size_in_bytes"])
    in_total = per_device["params"] + per_device["optimizer_state"] + per_device_batch
    expected_params = arg * per_device["params"] / in_total
    assert profile.bytes_by_class["params"] == pytest.approx(expected_params)
    # and the pro-rata anchor itself is the per-device sum: XLA's reported
    # argument bytes must be near it (padding only), nowhere near the
    # global sum.
    global_total = (
        global_classes["params"] + global_classes["optimizer_state"]
        + batch_class_bytes(batch)
    )
    assert arg == pytest.approx(in_total, rel=0.02)
    assert arg < 0.6 * global_total


def test_tree_shard_bytes_exact_on_hand_built_shardings(devices):
    """Hand-built FSDP layout: a [48, 512] f32 leaf sharded 4-way over fsdp
    is 48*512*4/4 bytes per device; a replicated [32] leaf stays whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_pytorch_tpu.parallel.sharding import tree_shard_bytes

    mesh = mesh_lib.create_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.FSDP_AXIS: 4}, devices=devices
    )
    tree = {
        "kernel": jax.ShapeDtypeStruct((48, 512), np.float32),
        "bias": jax.ShapeDtypeStruct((32,), np.float32),
    }
    shardings = {
        "kernel": NamedSharding(mesh, P(None, "fsdp")),
        "bias": NamedSharding(mesh, P()),
    }
    assert tree_shard_bytes(tree, shardings) == 48 * 512 * 4 / 4 + 32 * 4
    # single-sharding broadcast: everything replicated = global sum
    assert tree_shard_bytes(tree, NamedSharding(mesh, P())) == 48 * 512 * 4 + 32 * 4


def test_preflight_recommends_fsdp(devices):
    """On predicted OOM from a pure-data mesh, the recommendation set
    includes 'enable fsdp=N' — probed on with_mesh twins, so the
    recommended extent is one whose per-device peak actually fits."""
    dp_mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)
    engine, state = _wide_mlp_engine(dp_mesh)
    batch = synthetic_batch(32)
    full_peak = analyze_step_memory(engine, state, batch, top_k=0).peak_bytes
    fsdp2 = engine.with_mesh(
        mesh_lib.create_mesh(
            {mesh_lib.DATA_AXIS: 4, mesh_lib.FSDP_AXIS: 2}, devices=devices
        )
    )
    fsdp2_peak = analyze_step_memory(fsdp2, state, batch, top_k=0).peak_bytes
    assert fsdp2_peak < full_peak  # params dominate: sharding must help
    capacity = (fsdp2_peak + full_peak) // 2
    report = run_preflight(
        engine,
        state,
        batch,
        Preflight(capacity_bytes=int(capacity), headroom=0.0, action="warn"),
    )
    assert report.fits is False
    assert report.recommended_fsdp == 2
    # the recommendation is honest: the probed twin's peak fits capacity
    assert fsdp2_peak <= capacity


# ---------------------------------------------------------------------------
# Live telemetry: the shared memory_stats read degrades to absent on CPU.


def test_live_memory_degrades_to_absent_on_cpu(devices):
    from distributed_training_pytorch_tpu.memory import window_memory_fields

    assert device_memory_stats() is None  # CPU backend has no allocator stats
    assert live_memory_fields() == {}
    assert live_memory_fields(include_peak=False) == {}
    assert memory_skew() == {}
    assert window_memory_fields() == {}


def test_window_memory_fields_single_pass_consistency():
    """One sampling instant: live_bytes always sits within its own
    min/max (two separate reads could interleave with allocations and emit
    a self-contradictory record)."""
    from distributed_training_pytorch_tpu.memory import window_memory_fields

    class FakeDevice:
        def __init__(self, live):
            self._live = live

        def memory_stats(self):
            return {"bytes_in_use": self._live, "peak_bytes_in_use": self._live * 2}

    fields = window_memory_fields([FakeDevice(100), FakeDevice(300), FakeDevice(200)])
    assert fields["live_bytes"] == 100 and fields["peak_bytes"] == 200
    assert fields["live_bytes_min"] == 100 and fields["live_bytes_max"] == 300
    assert fields["live_bytes_skew"] == 200
    assert fields["live_bytes_min"] <= fields["live_bytes"] <= fields["live_bytes_max"]
    solo = window_memory_fields([FakeDevice(42)], include_peak=False)
    assert solo == {"live_bytes": 42}  # no skew fields on single-chip


@pytest.mark.parametrize("stats,want", [
    # no reserved figure reported: the allocator's own peak
    ({"bytes_in_use": 100, "peak_bytes_in_use": 250}, 250),
    # today's TPU runtime: a program's temporaries sit in bytes_reserved
    ({"bytes_in_use": 100, "peak_bytes_in_use": 120, "bytes_reserved": 400}, 500),
    ({"bytes_in_use": 100, "peak_bytes_in_use": 120, "bytes_reserved": 10,
      "peak_bytes_reserved": 700}, 800),
    # the allocator's peak still wins where it is the larger
    ({"bytes_in_use": 100, "peak_bytes_in_use": 900, "bytes_reserved": 400}, 900),
])
def test_peak_bytes_counts_reserved_temporaries(stats, want):
    """`peak_bytes` is the larger of peak_bytes_in_use and in-use + reserved
    (benchmarks/lib/harness.py:memory_peak_bytes's reckoning), through both
    field builders; a stats dict with no peak yields no field."""
    from distributed_training_pytorch_tpu.memory import window_memory_fields

    class FakeDevice:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return dict(self._stats)

    assert live_memory_fields(FakeDevice(stats))["peak_bytes"] == want
    assert window_memory_fields([FakeDevice(stats)])["peak_bytes"] == want
    assert "peak_bytes" not in live_memory_fields(FakeDevice(stats), include_peak=False)
    assert "peak_bytes" not in live_memory_fields(FakeDevice({"bytes_in_use": 7}))


def test_is_oom_error_classification():
    from jax.errors import JaxRuntimeError

    assert is_oom_error(JaxRuntimeError("RESOURCE_EXHAUSTED: 1.2GiB > 1.0GiB"))
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))
    # worded without the status name: classified by the runtime-error CLASS
    assert is_oom_error(JaxRuntimeError("Execution failed: Out of memory while trying"))
    # host-side failures are bugs to surface, not device fit boundaries
    assert not is_oom_error(MemoryError())
    assert not is_oom_error(Exception("Out of memory while trying"))
    assert not is_oom_error(ValueError("shapes do not match"))


# ---------------------------------------------------------------------------
# Memory-growth anomaly detector: leak fires, flat stays quiet.


def test_memory_growth_fires_on_injected_leak():
    detector = AnomalyDetector(warmup=2, memory_growth=1.5)
    fired = []
    live = 1000.0
    for step in range(20):
        live += 120.0  # a steady host-side leak
        fired += detector.observe(step, live_bytes=live)
    kinds = {a.kind for a in fired}
    assert kinds == {"memory_growth"}, fired
    first = fired[0]
    # the baseline is the steady-state floor, never dragged up by the leak
    assert first.value > 1.5 * first.baseline
    assert detector.total_fired == len(fired) > 0


def test_memory_growth_quiet_on_flat_run():
    detector = AnomalyDetector(warmup=2, memory_growth=1.5)
    rng = np.random.RandomState(0)
    for step in range(50):
        live = 1_000_000 + rng.randint(-5000, 5000)  # flat ± noise
        assert detector.observe(step, live_bytes=float(live)) == []
    assert detector.total_fired == 0


def test_memory_growth_warmup_allows_allocator_ramp():
    """The allocator legitimately ramps while caches/prefetch fill: warmup
    observations are untracked, so the floor is the steady state, not the
    cold start."""
    detector = AnomalyDetector(warmup=3, memory_growth=1.5)
    for step, live in enumerate([100.0, 10_000.0, 50_000.0, 100_000.0, 101_000.0, 99_000.0]):
        assert detector.observe(step, live_bytes=live) == []


def test_memory_growth_absent_value_never_fires():
    detector = AnomalyDetector(warmup=0, memory_growth=1.5)
    for step in range(10):
        assert detector.observe(step, live_bytes=None) == []
    disabled = AnomalyDetector(warmup=0, memory_growth=None)
    for step in range(10):
        assert disabled.observe(step, live_bytes=float(10 ** (step + 2))) == []


# ---------------------------------------------------------------------------
# Trainer integration: preflight=None parity, fail-fast, event + degradation.


def test_trainer_preflight_parity_and_event(tmp_path, mesh):
    """THE acceptance test: preflight observes, it does not alter —
    trace_counts identical and params bit-exact between preflight=None (the
    historical program) and a preflight-on run; the on run leaves one
    memory_preflight event with the attribution payload; on CPU the window
    records degrade to absent live-memory fields."""
    off = make_tiny(tmp_path / "off", mesh, telemetry="on", preflight=None)
    off.train()
    on = make_tiny(
        tmp_path / "on", mesh, telemetry="on",
        preflight=Preflight(capacity_bytes=1 << 50),
    )
    on.train()
    assert dict(on.engine.trace_counts) == dict(off.engine.trace_counts)
    assert_trees_equal(on.state.params, off.state.params)
    assert_trees_equal(on.state.opt_state, off.state.opt_state)
    assert off.memory_report is None and on.memory_report.fits is True
    events = list(
        read_events(os.path.join(on.save_folder, "telemetry", "events.jsonl"))
    )
    preflights = [e for e in events if e["event"] == "memory_preflight"]
    assert len(preflights) == 1
    record = preflights[0]
    assert record["fits"] is True
    assert record["chain_length"] == 2  # the chained window IS the program
    assert abs(sum(record["fractions"].values()) - 1.0) < 1e-3
    assert record["predicted_peak_bytes"] == on.memory_report.predicted_peak_bytes
    assert record["top_buffers"]
    # statless backend: window records carry no live-memory fields
    windows = [e for e in events if e["event"] == "window"]
    assert windows and all("live_bytes" not in w for w in windows)
    # the off run has no memory_preflight record at all
    off_events = list(
        read_events(os.path.join(off.save_folder, "telemetry", "events.jsonl"))
    )
    assert not [e for e in off_events if e["event"] == "memory_preflight"]


def test_trainer_preflight_short_epoch_predicts_single_step_program(tmp_path, mesh):
    """An epoch shorter than one chained window never dispatches the window
    program — the preflight verdict must cover the single-step program that
    actually runs, not a 4-batch window that never forms (which could fail
    a run whose real program fits)."""
    trainer = make_tiny(
        tmp_path, mesh,
        batch_size=16,  # 48 records -> 3 batches/epoch, below the window
        chain_steps=4,
        log_every=4,
        telemetry="on",
        preflight=Preflight(capacity_bytes=1 << 50),
    )
    trainer.train()
    assert trainer.memory_report is not None
    assert trainer.memory_report.chain_length is None
    assert trainer.memory_report.fits is True


def test_trainer_preflight_oom_fails_before_any_dispatch(tmp_path, mesh):
    trainer = make_tiny(
        tmp_path, mesh, preflight=Preflight(capacity_bytes=2048)
    )
    with pytest.raises(PreflightOOMError) as err:
        trainer.train()
    # fail-fast means FAST: nothing was ever compiled or dispatched
    assert dict(trainer.engine.trace_counts) == {}
    assert err.value.report.fits is False


def test_trainer_preflight_skipped_under_custom_train_step(tmp_path, mesh):
    from test_telemetry import TinyTrainer

    class CustomStep(TinyTrainer):
        def train_step(self, state, batch):
            return self.engine.train_step(state, batch)

    logs = []
    trainer = CustomStep(
        max_epoch=1, batch_size=8, have_validate=False,
        save_folder=str(tmp_path / "runs"), num_workers=0, log_every=0,
        chain_steps=1, async_checkpoint=False, mesh=mesh, progress=False,
        preflight=Preflight(capacity_bytes=1),  # would fail if it ran
        logger=type("L", (), {"log": staticmethod(lambda m, t="info": logs.append(m))})(),
    )
    trainer.train()  # does NOT raise: preflight skipped with a warning
    assert trainer.memory_report is None
    assert any("preflight skipped" in m for m in logs)
