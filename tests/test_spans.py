"""Spans and counters inside the trainer (ISSUE 25): the one primitive
(``profiling.trace.annotate`` / ``count`` under a recorder), where the
training path places it, the clock it stamps, and the scopes and kernel names
on the device side. CPU only; the kernels are lowered, never run."""

import functools
import importlib
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spans as spans_lib
from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.telemetry import Telemetry

from test_telemetry import TinyTrainer, _Quiet, assert_trees_equal

# the module: the package's attribute of that name is the `trace` context manager
trace = importlib.import_module("distributed_training_pytorch_tpu.profiling.trace")
MAIN = "MainThread"


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


@pytest.fixture
def no_recorder():
    """Each test starts and ends with no recorder: it is process-wide."""
    trace.uninstall_recorder()
    yield
    trace.uninstall_recorder()


# -- the primitive --------------------------------------------------------------


def test_recorder_off_keeps_nothing_and_annotate_still_nests(no_recorder):
    with trace.annotate("outer", unit=1) as outer:
        with trace.annotate("inner"):
            trace.count("things", 3)
        outer.set(traced=True)
    assert trace.recorded() == [] and trace.counters() == {}
    assert outer.ids == {"unit": 1, "traced": True}  # set() works either way; nothing is kept


def test_recorder_keeps_name_times_thread_parent_and_ids(no_recorder):
    trace.install_recorder()
    t0 = time.time_ns()
    with trace.annotate("outer", epoch=2) as outer:
        with trace.annotate("inner", unit=7):
            time.sleep(0.002)
        outer.set(traced=False)
    t1 = time.time_ns()
    inner, out = trace.recorded()  # in order of their ends
    assert (inner.name, inner.parent, inner.ids) == ("inner", "outer", {"unit": 7})
    assert (out.name, out.parent, out.ids) == ("outer", None, {"epoch": 2, "traced": False})
    assert inner.thread == out.thread == threading.current_thread().name
    assert t0 <= out.start_ns <= inner.start_ns < inner.end_ns <= out.end_ns <= t1
    assert inner.end_ns - inner.start_ns >= 2_000_000
    trace.install_recorder()  # idempotent: a second trainer adds to the same record
    assert len(trace.recorded()) == 2


def test_a_span_that_raises_is_kept_and_hands_the_thread_back_to_its_parent(no_recorder):
    trace.install_recorder()
    with trace.annotate("outer"):
        with pytest.raises(KeyError):
            with trace.annotate("fails"):
                raise KeyError("x")
        with trace.annotate("after"):
            pass
    assert [(s.name, s.parent) for s in trace.recorded()] == [
        ("fails", "outer"), ("after", "outer"), ("outer", None)]


def test_counters_total_and_in_a_stretch_of_time(no_recorder):
    trace.install_recorder()
    trace.count("a")
    trace.count("b", 2.5)
    mid = time.time_ns()
    time.sleep(0.001)
    trace.count("a", 4)
    assert trace.counters() == {"a": 5, "b": 2.5}
    assert trace.counters(until_ns=mid) == {"a": 1, "b": 2.5}
    assert trace.counters(since_ns=mid) == {"a": 4}
    assert trace.counters(since_ns=mid, until_ns=mid) == {}


def test_the_record_is_bounded(no_recorder, monkeypatch):
    monkeypatch.setattr(trace, "MAX_SPANS", 8)
    trace.install_recorder()
    for i in range(20):
        with trace.annotate("s", unit=i):
            trace.count("c")
    assert [s.ids["unit"] for s in trace.recorded()] == list(range(12, 20))  # the oldest fell off
    assert trace.counters() == {"c": 20}  # totals are exact all the same
    assert trace.counters(since_ns=0) == {"c": 8}


def test_threads_record_side_by_side_without_losing_a_span_or_a_count(no_recorder):
    """More threads than cores, a short switch interval: every span and count
    arrives, and a parent is always of the span's own thread."""
    trace.install_recorder()
    workers, each = 4 * (os.cpu_count() or 2), 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for i in range(each):
                with trace.annotate(f"outer.{k}"):
                    with trace.annotate(f"inner.{k}", unit=i):
                        trace.count("n")
        threads = [threading.Thread(target=work, args=(k,), name=f"w{k}") for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    got = trace.recorded()
    assert len(got) == 2 * workers * each and trace.counters() == {"n": workers * each}
    for s in got:
        k = s.name.split(".")[1]
        assert s.thread == f"w{k}" and s.parent == (f"outer.{k}" if s.name.startswith("inner") else None)


# -- the clock ---------------------------------------------------------------------


def test_a_span_reads_the_same_start_in_the_trace_and_in_the_recorder(no_recorder, tmp_path):
    """Measured, not assumed: the profiler stamps host events with the realtime
    clock and shifts the whole trace so that its session's start reads 0; the
    shift is the xplane's `profile_start_time`. A recorded span minus that
    shift is the same span in the trace's host plane, to well under 100 us."""
    from jax.profiler import ProfileData

    trace.install_recorder()
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(20):
            with trace.annotate("clock.probe", unit=i):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    zero_ns = trace.session_start_ns(str(tmp_path))
    assert zero_ns is not None and abs(zero_ns - time.time_ns()) < 600e9  # realtime, not a monotonic clock
    in_trace = {}
    for plane in ProfileData.from_file(trace.latest_trace_file(str(tmp_path))).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "clock.probe":
                    in_trace[dict(ev.stats)["unit"]] = (ev.start_ns, ev.duration_ns)  # the ids ride as stats
    kept = {s.ids["unit"]: s for s in trace.recorded() if s.name == "clock.probe"}
    assert sorted(in_trace) == sorted(kept) == list(range(20))
    starts = sorted(abs(in_trace[i][0] - (kept[i].start_ns - zero_ns)) for i in kept)
    ends = sorted(abs(in_trace[i][0] + in_trace[i][1] - (kept[i].end_ns - zero_ns)) for i in kept)
    # the median: one probe in twenty may lose the processor between the two stamps
    # (here 2-4 us: the profiler's clock is absl's, which follows the kernel's realtime clock to a few us)
    assert starts[len(starts) // 2] < 100e3 and ends[len(ends) // 2] < 100e3, (starts, ends)


# -- where the training path places them ------------------------------------------------


class BatchSource(ArrayDataSource):
    """A source that makes its batches whole (the loader's fast path), so a
    `loader.batch` span is a pool worker's."""

    def load_batch(self, rows, epoch):
        return {k: v[rows] for k, v in self.arrays.items()}


class SpanTrainer(TinyTrainer):
    """Marks every `train_epoch` on the goodput meter and on the spans' clock,
    and notes by how much each call into the engine raised `trace_counts`."""

    def __init__(self, **kw):
        self.epoch_marks, self.rises = [], []
        super().__init__(**kw)
        for name in ("train_steps_chained", "train_step"):
            setattr(self.engine, name, functools.partial(self._noting, getattr(self.engine, name)))

    def _noting(self, call, *args):
        before = sum(self.engine.trace_counts.values())
        out = call(*args)
        self.rises.append(sum(self.engine.trace_counts.values()) - before)
        return out

    def build_train_dataset(self):
        return BatchSource(**super().build_train_dataset().arrays)

    def train_epoch(self, epoch):
        if self.goodput is None:
            return super().train_epoch(epoch)
        self.goodput.tick("other")
        before, t0 = dict(self.goodput.buckets), time.time_ns()
        out = super().train_epoch(epoch)
        self.goodput.tick("other")
        self.epoch_marks.append(
            (t0, time.time_ns(), {k: v - before[k] for k, v in self.goodput.buckets.items()}))
        return out


def quiet_telemetry():
    return Telemetry(stats=False, goodput=True, mfu=False, anomaly=None, memory=False,
                     straggler=False, heartbeat_every_s=0.0)


def make_span_trainer(tmp_path, mesh, **kw):
    """48 records in batches of 8, chained x4: each epoch is one window and a
    two-step tail, with the `log_every` sync after the window."""
    return SpanTrainer(**dict(
        max_epoch=2, batch_size=8, chain_steps=4, log_every=4, num_workers=2, have_validate=False,
        save_best_for=None, save_period=None, save_folder=str(tmp_path / "runs"),
        async_checkpoint=False, mesh=mesh, progress=False, logger=_Quiet(), **kw))


@pytest.fixture(scope="module")
def span_run(tmp_path_factory, mesh):
    """One telemetry-on run; what it recorded."""
    trace.uninstall_recorder()
    trainer = make_span_trainer(tmp_path_factory.mktemp("span_run"), mesh, telemetry=quiet_telemetry())
    trainer.train()
    kept, counted = trace.recorded(), trace.counters()
    trace.uninstall_recorder()
    return trainer, kept, counted


def by_name(kept, name):
    return sorted((s for s in kept if s.name == name), key=lambda s: s.start_ns)


def test_parent_and_thread_across_main_prefetch_and_pool_threads(span_run):
    _, kept, _ = span_run
    parents = {
        "trainer.init": (None, MAIN), "engine.init_state": ("trainer.init", MAIN),
        "trainer.build_loaders": ("trainer.init", MAIN), "trainer.train": (None, MAIN),
        "trainer.epoch": ("trainer.train", MAIN), "trainer.epoch_start": ("trainer.epoch", MAIN),
        "trainer.fetch": ("trainer.epoch", MAIN), "engine.dispatch": ("trainer.epoch", MAIN),
        "trainer.sync": ("trainer.epoch", MAIN), "trainer.epoch_end": ("trainer.epoch", MAIN),
        "trainer.checkpoint": ("trainer.train", MAIN),
    }
    for name, (parent, thread) in parents.items():
        found = by_name(kept, name)
        assert found, name
        assert {(s.parent, s.thread) for s in found} == {(parent, thread)}, name
    staged, made = by_name(kept, "prefetch.stage"), by_name(kept, "loader.batch")
    assert {(s.parent, s.thread) for s in staged} == {(None, "device-prefetch")}
    assert {s.parent for s in made} == {None}
    assert all(s.thread.startswith("ThreadPoolExecutor") for s in made)  # a pool worker's, not the caller's
    assert {s.name for s in kept} == set(parents) | {"prefetch.stage", "loader.batch"}
    # every span of a thread nests properly in time under its root
    assert len(by_name(kept, "trainer.train")) == 1 and len(by_name(kept, "trainer.epoch")) == 2
    root = by_name(kept, "trainer.train")[0]
    for s in kept:
        if s.thread == MAIN and s.name != "trainer.init" and s.parent != "trainer.init":
            assert root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns, s


def test_unit_ids_join_loader_stage_fetch_and_dispatch(span_run):
    _, kept, _ = span_run
    for epoch in (0, 1):
        of = lambda name: [s for s in by_name(kept, name) if s.ids["epoch"] == epoch]  # noqa: E731
        units = [(s.ids["unit"], s.ids["steps"]) for s in of("engine.dispatch")]
        # 6 steps an epoch, chained x4: the window and two single steps; `unit` is the global step of the first
        assert units == [(6 * epoch + k, n) for k, n in ((0, 4), (4, 1), (5, 1))]
        staged = of("prefetch.stage")
        assert [(s.ids["unit"], s.ids["steps"]) for s in staged] == units
        assert [s.ids["batch"] for s in staged] == [0, 4, 5]
        # a fetch per unit, and the one that found the ring finished
        assert [s.ids["unit"] for s in of("trainer.fetch")] == [u for u, _ in units] + [6 * epoch + 6]
        made = {s.ids["batch"]: s for s in of("loader.batch")}
        assert sorted(made) == list(range(6))
        for stage, fetch, dispatch in zip(staged, of("trainer.fetch"), of("engine.dispatch")):
            batches = [made[stage.ids["batch"] + i] for i in range(stage.ids["steps"])]
            # one unit's way through the threads, in time order
            assert max(b.end_ns for b in batches) <= stage.start_ns
            assert stage.end_ns <= fetch.end_ns <= dispatch.start_ns


def test_dispatch_traced_is_true_exactly_where_trace_counts_rose(span_run):
    trainer, kept, _ = span_run
    dispatches = by_name(kept, "engine.dispatch")
    assert len(dispatches) == len(trainer.rises) == 6
    for span, rose in zip(dispatches, trainer.rises):
        assert span.ids["traced"] is (rose > 0), (span, rose)
    # the chained program and the tail step each trace once, in the first epoch
    assert [s.ids["traced"] for s in dispatches] == [True, True, False] + [False] * 3
    assert dict(trainer.engine.trace_counts) == {"chained_4": 1, "train_step": 1}


def test_ring_counters_count_every_fetch(span_run):
    _, kept, counted = span_run
    assert counted["prefetch.fetches"] == len(by_name(kept, "trainer.fetch")) == 8
    assert 1 <= counted.get("prefetch.fetches_empty", 0) <= counted["prefetch.fetches"]


def test_span_self_times_reproduce_the_goodput_buckets(span_run):
    """One system, two views: over each `train_epoch` the main thread's spans,
    grouped fetch / dispatch + sync / the rest, give the meter's bucket deltas."""
    trainer, kept, _ = span_run
    assert len(trainer.epoch_marks) == 2
    for t0, t1, buckets in trainer.epoch_marks:
        inside = [s for s in kept if s.thread == MAIN and t0 <= s.start_ns and s.end_ns <= t1]
        own = spans_lib.self_ns(inside)
        fetch = own.get("trainer.fetch", 0) / 1e9
        step = (own.get("engine.dispatch", 0) + own.get("trainer.sync", 0)) / 1e9
        rest = (t1 - t0) / 1e9 - fetch - step
        assert abs(sum(buckets.values()) - (t1 - t0) / 1e9) < 1e-3  # the meter covers the same stretch
        room = max(0.02 * (t1 - t0) / 1e9, 5e-3)
        assert abs(fetch - buckets["data_wait"]) < room, (fetch, buckets)
        assert abs(step - buckets["productive_step"] - buckets["compile"]) < room, (step, buckets)
        assert abs(rest - buckets["other"] - buckets["checkpoint"]) < room, (rest, buckets)


def test_telemetry_on_is_bit_identical_to_off(tmp_path, mesh, no_recorder):
    off = make_span_trainer(tmp_path / "off", mesh)
    assert trace.recorded() == []
    off.train()
    assert trace.recorded() == []  # telemetry off: no recorder, nothing kept
    on = make_span_trainer(tmp_path / "on", mesh, telemetry=quiet_telemetry())
    on.train()
    assert len(trace.recorded()) > 30
    assert_trees_equal(on.state.params, off.state.params)
    assert dict(on.engine.trace_counts) == dict(off.engine.trace_counts)


# -- on the device: scopes and kernel names -------------------------------------------------


def test_the_lowered_step_names_the_loss_head_and_the_optimizer(devices, monkeypatch):
    """HLO metadata only: every matmul of the fused tied-CE head, the `while`
    that holds them and the backward half's scaling (the `custom_vjp`'s bwd
    rule, which no autodiff names) carry `loss_head`; the update `optimizer`."""
    import optax

    from distributed_training_pytorch_tpu.analysis import hlo_audit
    from distributed_training_pytorch_tpu.ops import losses
    from distributed_training_pytorch_tpu.precision import DynamicScale
    from distributed_training_pytorch_tpu.train import TrainEngine

    monkeypatch.setattr(losses, "_SLICE_LOGITS_BYTES", 4 * 2 * 300 * 4)  # 2 rows a chip x 4 tokens: two slices

    def loss_fn(params, model_state, batch, rng, train):
        hidden = jnp.tanh(batch["image"] @ params["w"])
        loss = losses.tied_cross_entropy_loss(hidden, params["emb"], batch["label"], batch["mask"])
        return loss, ({"loss": loss}, model_state)

    # a dynamic loss scale: the head's cotangent is a traced scalar, so the backward's scaling is in the program
    engine = TrainEngine(loss_fn, optax.adamw(1e-3), mesh_lib.create_mesh(), precision="fp16",
                         loss_scale=DynamicScale.create())
    state = engine.init_state(jax.random.key(0), lambda rng: {"params": {
        "w": jnp.ones((16, 32)) * 0.1, "emb": jax.random.normal(rng, (300, 32))}})
    batch = {"image": np.ones((2, 16, 8, 16), np.float32), "label": np.zeros((2, 16, 8), np.int32),
             "mask": np.ones((2, 16), np.float32)}  # 2 steps of 16 rows (2 a chip) x 8 tokens
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    with engine._ambient_mesh():  # as a dispatch sets it: the head sizes its slices by a chip's rows
        lowered = engine._chained_step_fn(2, state).lower(state, batch)
    # the lowered module names an op relative to its computation: one is the head's if the
    # instruction that calls it is
    comps = hlo_audit.computations(lowered.as_text(dialect="hlo", debug_info=True))
    heads = hlo_audit.called_from(comps, lambda ln: "loss_head" in ln)
    matmuls = [(re.search(r'op_name="([^"]*)"', ln).group(1), name in heads or "loss_head" in ln)
               for name, lines in comps.items() for ln in lines if " dot(" in ln]
    in_head = sorted(op.split("/")[0] for op, scoped in matmuls if scoped)
    assert in_head == ["crsd,vd->crsv", "crsv,crsd->cvd", "crsv,vd->crsd"], matmuls  # three, none twice
    assert [op for op, scoped in matmuls if not scoped and "->" in op] == [], matmuls  # the model's own only
    text = lowered.compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    whiles = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in text.splitlines() if " while(" in ln]
    assert len(whiles) == 2 and all("loss_head" in w for w in whiles), whiles  # one loop a step
    assert any("transpose(jvp(" in n and "loss_head" in n for n in names)  # the bwd rule's scaling
    assert any("/optimizer/" in n for n in names)
    assert not any("optimizer" in n and "loss_head" in n for n in names)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dqkv", "conv1x1_bn_act"])
def test_each_kernel_is_lowered_under_its_name(kernel):
    """Lowered for the TPU (nothing compiles or runs): the Mosaic call carries
    `kernel_name`, and the op's location the scope of the same name."""
    from distributed_training_pytorch_tpu.ops import pallas

    if kernel == "conv1x1_bn_act":
        x, w = jnp.ones((4, 8, 8, 128), jnp.bfloat16), jnp.ones((128, 256), jnp.bfloat16)
        fn = jax.jit(lambda x, w: pallas.conv1x1_bn_act(
            x, w, jnp.ones((256,)), jnp.zeros((256,)), block_rows=256, interpret=False))
        args = (x, w)
    else:
        q = jnp.ones((2, 256, 2, 64), jnp.bfloat16)
        fn = jax.jit(jax.grad(lambda q, k, v: pallas.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        args = (q, q, q)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert f'kernel_name = "{kernel}"' in text
    assert re.search(rf'loc\("[^"]*\b{kernel}\b[^"]*"', text), kernel
