"""ISSUE 18 tests: the serving subsystem — continuous micro-batching on
the training machinery.

Acceptance pillars:

* the :class:`serving.batcher.MicroBatcher` flush policy, pinned at its
  boundaries WITHOUT devices (pure Python, injected clock): bucket
  boundary-exactness, deadline flush at exactly ``max_delay_s``, full
  flush the instant the largest bucket fills, round-robin fairness under
  a greedy tenant, typed + counted overload, and the zero-capacity
  refuse-not-hang contract;
* :class:`serving.engine.InferEngine` mirrors ``TrainEngine``'s executable
  contract: one compile per (bucket, row signature) with ``trace_counts``
  bumped in-body, a structure-checked one-engine-one-model binding,
  bucket/mesh-extent validation up front, and bit-identical outputs for
  identical params across a hot-swap (the soak's determinism leg, unit
  sized);
* :class:`serving.server.InferenceServer` end to end on the virtual CPU
  mesh: /predict, /status, /metrics, HTTP 429 on overload, the
  ``serve_start``/``request_batch``/``hot_swap``/``admission_reject``
  flight-recorder vocabulary, and hot-swap under load via a manifest
  identity change;
* the monitor reads a server run as a first-class fleet member (status
  ``serving``, verdict ``healthy``/``slo_breach``, qps/p99 fleet columns)
  and the fleet controller's mixed-fleet ``offer_chip`` advisory;
* import neutrality: ``distributed_training_pytorch_tpu.serving`` pulls
  NO jax at package import — a trainer that imports-but-ignores serving
  cannot perturb a training run.
"""

import json
import os
import subprocess
import sys
import time
import types
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distributed_training_pytorch_tpu.parallel import mesh_config_from_spec
from distributed_training_pytorch_tpu.serving import (
    MicroBatcher,
    OverloadRejected,
    pick_bucket,
)
from distributed_training_pytorch_tpu.serving.engine import InferEngine
from distributed_training_pytorch_tpu.serving.server import (
    InferenceServer,
    LatencyWindow,
)
from distributed_training_pytorch_tpu.telemetry.events import (
    resolve_events_path,
)
from distributed_training_pytorch_tpu.telemetry.monitor import (
    AlertConfig,
    RunMonitor,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# pick_bucket: boundary exactness.


def test_pick_bucket_boundary_exact():
    buckets = (1, 2, 4, 8)
    assert pick_bucket(1, buckets) == 1
    assert pick_bucket(2, buckets) == 2
    assert pick_bucket(3, buckets) == 4
    assert pick_bucket(4, buckets) == 4  # exactly on a boundary: that bucket
    assert pick_bucket(5, buckets) == 8  # one over: the next
    assert pick_bucket(8, buckets) == 8
    with pytest.raises(ValueError):
        pick_bucket(9, buckets)
    with pytest.raises(ValueError):
        pick_bucket(0, buckets)


# ---------------------------------------------------------------------------
# MicroBatcher: the flush policy on a fake clock.


def _batcher(**kw):
    kw.setdefault("buckets", (1, 2, 4, 8))
    kw.setdefault("max_delay_s", 0.02)
    kw.setdefault("clock", FakeClock())
    return MicroBatcher(**kw)


def test_deadline_flush_exact():
    clock = FakeClock()
    b = _batcher(clock=clock)
    for _ in range(3):
        b.submit("a", 0)
    # Just under the deadline: keep admitting.
    clock.advance(0.019)
    assert b.next_batch() is None
    # At the deadline: flush, padded to the covering bucket.
    clock.advance(0.001)
    batch = b.next_batch()
    assert batch is not None
    assert batch.flushed_by == "deadline"
    assert len(batch.requests) == 3
    assert batch.bucket == 4 and batch.pad == 1
    assert b.pending() == 0


def test_full_flush_immediate():
    b = _batcher()
    for _ in range(8):
        b.submit("a", 0)
    # No clock advance at all: the largest bucket is occupied, flush now.
    batch = b.next_batch()
    assert batch is not None
    assert batch.flushed_by == "full"
    assert batch.bucket == 8 and batch.pad == 0


def test_next_deadline_tracks_oldest():
    clock = FakeClock(100.0)
    b = _batcher(clock=clock)
    assert b.next_deadline() is None
    b.submit("a", 0)
    assert b.next_deadline() == pytest.approx(100.02)
    clock.advance(0.01)
    b.submit("b", 0)  # younger request must not push the deadline back
    assert b.next_deadline() == pytest.approx(100.02)


def test_fairness_greedy_tenant_cannot_starve_quiet_one():
    b = _batcher(max_queue_depth=200)
    for _ in range(100):
        b.submit("greedy", "g")
    for _ in range(4):
        b.submit("quiet", "q")
    batch = b.next_batch()  # full flush at bucket 8
    assert batch is not None and batch.bucket == 8
    by_tenant = {}
    for r in batch.requests:
        by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
    # Round-robin drafting: the quiet tenant gets every slot it can fill.
    assert by_tenant == {"greedy": 4, "quiet": 4}


def test_rotation_rotates_the_draft_start():
    b = _batcher(buckets=(1,), max_queue_depth=8)
    for _ in range(2):
        b.submit("a", 0)
        b.submit("b", 0)
    order = [b.next_batch(drain=True).requests[0].tenant for _ in range(4)]
    # The rotation start advances per batch: strict alternation, so no
    # tenant is structurally first in every single-slot bucket.
    assert order == ["a", "b", "a", "b"]


def test_fifo_within_tenant():
    b = _batcher()
    r1 = b.submit("a", "first")
    r2 = b.submit("a", "second")
    batch = b.next_batch(drain=True)
    ids = [r.id for r in batch.requests if r.tenant == "a"]
    assert ids == sorted(ids) and ids == [r1.id, r2.id]


def test_overload_typed_and_counted():
    b = _batcher(max_queue_depth=2)
    b.submit("a", 0)
    b.submit("a", 0)
    with pytest.raises(OverloadRejected) as exc:
        b.submit("a", 0)
    assert exc.value.tenant == "a"
    assert exc.value.depth == 2 and exc.value.bound == 2
    assert b.rejected["a"] == 1
    assert b.submitted == 2  # the rejected request was never admitted
    # Another tenant still has room: bounds are per tenant.
    b.submit("b", 0)
    assert b.pending() == 3


def test_submit_many_all_or_nothing():
    b = _batcher(max_queue_depth=4)
    b.submit("a", 0)
    # 3 more rows fit exactly; a 4-row request must not half-admit.
    with pytest.raises(OverloadRejected) as exc:
        b.submit_many("a", [1, 2, 3, 4])
    assert exc.value.depth == 1 and exc.value.bound == 4
    assert b.pending() == 1  # no orphan rows from the rejected request
    assert b.submitted == 1 and b.rejected["a"] == 1
    reqs = b.submit_many("a", [1, 2, 3])
    assert len(reqs) == 3 and b.pending() == 4
    assert [r.id for r in reqs] == sorted(r.id for r in reqs)  # FIFO ids
    assert b.submit_many("a", []) == []  # empty list: no-op, not a reject


def test_zero_capacity_refuses_never_hangs():
    b = _batcher(max_queue_depth=0)
    t0 = time.monotonic()
    with pytest.raises(OverloadRejected):
        b.submit("anyone", 0)
    assert time.monotonic() - t0 < 1.0  # refused, not queued/blocked
    assert b.rejected["anyone"] == 1 and b.pending() == 0


def test_drain_flush_reason_and_counters():
    b = _batcher()
    b.submit("a", 0)
    batch = b.next_batch(drain=True)
    assert batch.flushed_by == "drain"
    assert b.flushes == {"drain": 1}
    stats = b.stats()
    assert stats["batches"] == 1 and stats["pending"] == 0
    assert stats["padded_slots"] == 0  # 1 request -> bucket 1


def test_stats_pad_frac():
    b = _batcher()
    for _ in range(3):
        b.submit("a", 0)
    b.next_batch(drain=True)  # 3 -> bucket 4, one padded slot
    assert b.stats()["pad_frac"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# LatencyWindow.


def test_latency_window_quantiles_and_qps():
    clock = FakeClock(0.0)
    w = LatencyWindow(window_s=10.0, clock=clock)
    for i in range(100):
        w.add(float(i) * 0.05, latency_ms=float(i + 1))
    clock.t = 5.0
    snap = w.snapshot()
    assert snap["window_n"] == 100
    assert snap["p50_ms"] == 51.0
    assert snap["p99_ms"] == 100.0
    assert snap["qps"] == pytest.approx(20.0, rel=0.05)
    # Old completions age out of the trailing window.
    clock.t = 50.0
    assert w.snapshot()["window_n"] == 0


# ---------------------------------------------------------------------------
# InferEngine on the virtual CPU mesh.


@pytest.fixture(scope="module")
def tp_mesh(devices=None):
    # tensor=2 over two devices: batch-shard extent 1, so every bucket is
    # legal — and the TP path exercises the ambient-mesh/sharding plumbing.
    return mesh_config_from_spec("tp2").build(jax.devices()[:2])


def _linear_params(seed=0, d=4):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((d, d)).astype(np.float32)}


def _linear_apply(params, x):
    return x @ params["w"]


def test_engine_bucket_extent_validation():
    dp8 = mesh_config_from_spec("dp8").build()
    with pytest.raises(ValueError, match="batch-shard extent"):
        InferEngine(_linear_apply, dp8, buckets=(1, 2, 4, 8))
    # Buckets the extent divides are fine.
    InferEngine(_linear_apply, dp8, buckets=(8, 16))


def test_engine_pads_dispatches_and_never_retraces(tp_mesh):
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4, 8))
    with pytest.raises(RuntimeError, match="no params"):
        eng.predict(np.ones((1, 4), np.float32))
    params = _linear_params()
    eng.swap_params(params, version="v1")
    eng.warmup(np.ones((4,), np.float32))
    assert eng.trace_counts["infer_step"] == 4  # one trace per bucket
    x = np.random.default_rng(1).standard_normal((3, 4)).astype(np.float32)
    out, version = eng.predict(x)
    assert version == "v1"
    assert out.shape == (3, 4)  # pad to bucket 4, sliced back off
    np.testing.assert_allclose(out, x @ params["w"], rtol=1e-5)
    # Steady state: same signatures, zero new traces (the retrace guard).
    for n in (1, 2, 3, 5, 8):
        eng.predict(np.ones((n, 4), np.float32))
    assert eng.trace_counts["infer_step"] == 4


def test_engine_structure_check_one_engine_one_model(tp_mesh):
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2))
    eng.swap_params(_linear_params(), version="v1")
    with pytest.raises(ValueError, match="different structure"):
        eng.swap_params({"w": np.ones((8, 8), np.float32)}, version="v2")
    with pytest.raises(ValueError, match="different structure"):
        eng.swap_params({"other": np.ones((4, 4), np.float32)}, version="v2")


def test_engine_same_params_same_bytes_across_swap(tp_mesh):
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4))
    params = _linear_params(seed=7)
    x = np.random.default_rng(3).standard_normal((3, 4)).astype(np.float32)
    eng.swap_params(params, version="best@e1")
    a, _ = eng.predict(x)
    # Hot-swap to an IDENTICAL params tree (a re-commit of the same
    # checkpoint): responses must be bit-identical, not merely close.
    eng.swap_params({k: v.copy() for k, v in params.items()}, version="best@e1")
    b, _ = eng.predict(x)
    assert a.tobytes() == b.tobytes()
    assert eng.swap_count == 2
    # Different params must actually change the answer (the swap is real).
    eng.swap_params(_linear_params(seed=8), version="best@e2")
    c, v = eng.predict(x)
    assert v == "best@e2" and a.tobytes() != c.tobytes()


# ---------------------------------------------------------------------------
# InferenceServer end to end (ephemeral port, virtual CPU mesh).


def _post(port, payload, timeout=10.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(port, route, timeout=10.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{route}", timeout=timeout
    ) as resp:
        return resp.status, resp.read().decode()


@pytest.fixture()
def served(tmp_path, tp_mesh):
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4))
    eng.swap_params(_linear_params(seed=5), version="best@e1")
    eng.warmup(np.ones((4,), np.float32))
    server = InferenceServer(
        eng,
        batcher=MicroBatcher(buckets=(1, 2, 4), max_delay_s=0.005),
        run_dir=str(tmp_path),
        slo_p99_ms=2000.0,
        pulse_every_s=0.2,
        process_index=0,
    ).start()
    assert server.enabled and server.port
    try:
        yield server
    finally:
        server.close()


def test_server_predict_status_metrics(served, tmp_path):
    x = [[1.0, 2.0, 3.0, 4.0], [4.0, 3.0, 2.0, 1.0]]
    code, body = _post(served.port, {"tenant": "t0", "inputs": x})
    assert code == 200
    assert body["params_version"] == "best@e1"
    expect, _ = served.engine.predict(np.asarray(x, np.float32))
    np.testing.assert_allclose(np.asarray(body["outputs"]), expect, rtol=1e-6)
    # The response body is a pure function of (inputs, params): a second
    # identical request returns byte-identical JSON (hot-swap bit-identity
    # rests on this).
    code2, body2 = _post(served.port, {"tenant": "t0", "inputs": x})
    assert code2 == 200 and body2 == body

    code, text = _get(served.port, "/status")
    snap = json.loads(text)
    assert code == 200
    assert snap["kind"] == "server"
    assert snap["requests_total"] >= 4
    assert snap["params_version"] == "best@e1"
    assert snap["qps_per_chip"] >= 0.0
    code, text = _get(served.port, "/metrics")
    assert code == 200
    assert "tpu_serve_up 1" in text
    assert "tpu_serve_requests_total" in text
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(served.port, "/nonsense")
    assert exc.value.code == 404


def test_server_bad_request_is_400(served):
    code, body = _post(served.port, {"tenant": "t0"})  # no inputs
    assert code == 400 and body["error"] == "bad_request"


def test_server_overload_is_typed_429(tmp_path, tp_mesh):
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4))
    eng.swap_params(_linear_params(), version="v1")
    with InferenceServer(
        eng,
        batcher=MicroBatcher(buckets=(1, 2, 4), max_queue_depth=0),
        run_dir=str(tmp_path / "overloaded"),
        process_index=0,
    ) as server:
        server.start()
        t0 = time.monotonic()
        code, body = _post(server.port, {"tenant": "t9", "inputs": [[1, 2, 3, 4]]})
        assert time.monotonic() - t0 < 5.0  # refused, not hung
        assert code == 429
        assert body == {"error": "overload", "tenant": "t9", "depth": 0, "bound": 0}
    recs = _read_events(str(tmp_path / "overloaded"))
    rejects = [r for r in recs if r["event"] == "admission_reject"]
    assert len(rejects) == 1
    assert rejects[0]["tenant"] == "t9" and rejects[0]["rejected_total"] == 1


def test_server_multi_row_429_leaves_no_orphans(tmp_path, tp_mesh):
    """A rejected multi-row POST admits nothing: no already-queued rows
    keep dispatching (and burning compute) after the client's 429."""
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4))
    eng.swap_params(_linear_params(), version="v1")
    with InferenceServer(
        eng,
        batcher=MicroBatcher(buckets=(1, 2, 4), max_queue_depth=2, max_delay_s=5.0),
        run_dir=str(tmp_path / "orphans"),
        process_index=0,
    ) as server:
        server.start()
        x3 = [[1.0, 2.0, 3.0, 4.0]] * 3  # 3 rows > depth bound of 2
        code, body = _post(server.port, {"tenant": "t0", "inputs": x3})
        assert code == 429 and body["error"] == "overload"
        assert body["depth"] == 0 and body["bound"] == 2
        assert server.batcher.pending() == 0  # nothing half-admitted
        assert server.batcher.submitted == 0
        # The bound still admits a request that fits, whole.
        code, body = _post(server.port, {"tenant": "t0", "inputs": x3[:2]})
        assert code == 200 and len(body["outputs"]) == 2


def test_mixed_shape_batch_survives_dispatch(tmp_path, tp_mesh):
    """Two tenants posting valid rows of different lengths can land in one
    micro-batch; the dispatch thread must answer (not die on np.stack), and
    the well-shaped rows must succeed rather than fail for a neighbor."""
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2, 4))
    params = _linear_params(seed=5)
    eng.swap_params(params, version="v1")
    with InferenceServer(
        eng,
        batcher=MicroBatcher(buckets=(1, 2, 4), max_delay_s=0.2),
        run_dir=str(tmp_path / "mixed"),
        process_index=0,
    ) as server:
        server.start()
        # Submit straight into the batcher so both rows share a batch
        # deterministically (the HTTP path cannot force the timing).
        good = server.batcher.submit("a", np.ones((4,), np.float32))
        bad = server.batcher.submit("b", np.ones((8,), np.float32))
        assert good.wait(10.0) and bad.wait(10.0)
        assert good.error is None
        np.testing.assert_allclose(
            np.asarray(good.result), np.ones((4,), np.float32) @ params["w"],
            rtol=1e-5,
        )
        assert bad.error is not None  # answered as a failure, not a hang
        # The dispatch thread survived: the server still serves.
        code, body = _post(server.port, {"inputs": [[1.0, 0.0, 0.0, 0.0]]})
        assert code == 200 and body["params_version"] == "v1"


def test_default_batcher_inherits_server_clock(tp_mesh):
    """Latency is server-clock-now minus Request.arrival: the batcher the
    server builds for itself must stamp arrivals on the same clock."""
    clock = FakeClock(42.0)
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2))
    server = InferenceServer(eng, process_index=0, clock=clock)
    assert server.batcher._clock is clock
    assert server.batcher.submit("t", 0).arrival == 42.0


def _read_events(run_dir):
    path = resolve_events_path(run_dir)
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_server_flight_recorder_and_monitor_integration(served, tmp_path):
    # Traffic + a pulse interval's worth of wall time.
    for _ in range(3):
        _post(served.port, {"tenant": "a", "inputs": [[1.0, 0.0, 0.0, 0.0]]})
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        recs = _read_events(str(tmp_path))
        if any(r["event"] == "request_batch" for r in recs):
            break
        time.sleep(0.05)
    recs = _read_events(str(tmp_path))
    kinds = [r["event"] for r in recs]
    assert kinds[0] == "serve_start"
    start = recs[0]
    assert start["port"] == served.port and start["attempt"] == 1
    assert start["params_version"] == "best@e1"
    pulses = [r for r in recs if r["event"] == "request_batch"]
    assert pulses, f"no request_batch pulse in {kinds}"
    assert pulses[-1]["slo_ok"] is True  # 2 s SLO vs sub-ms linear model

    # The monitor reads this run dir as a serving fleet member.
    mon = RunMonitor(str(tmp_path), AlertConfig(stale_after_s=30.0))
    st = mon.poll()
    assert st.kind == "serve"
    assert st.status == "serving"
    assert st.verdict == "healthy"
    assert st.exit_code == 0
    row = st.fleet_row()
    assert row["qps"] != "?" and row["p99"] != "?"
    assert row["step_ms"] == "-" and row["good%"] == "-"  # trainer-only cols

    # Closing the server emits run_end -> the monitor's finished marker.
    served.close()
    st = RunMonitor(str(tmp_path), AlertConfig()).poll()
    assert st.status == "finished" and st.exit_code == 0


def test_server_hot_swap_under_load(tmp_path, tp_mesh):
    """A manifest identity change mid-traffic swaps params atomically:
    same params -> byte-identical responses, new params -> new answers,
    and a ``hot_swap`` record lands in the flight recorder."""
    ckpt_root = tmp_path / "weights"
    run_dir = tmp_path / "run"

    class StubState:
        def __init__(self, params):
            self.params = params

    class StubManager:
        """The manifest surface the swap watcher reads: exists/path/
        latest_valid_name/restore, driven by a plain dict."""

        MANIFEST = "manifest.json"

        def __init__(self):
            self.store = {}  # name -> (params, epoch)

        def commit(self, name, params, epoch):
            d = ckpt_root / name
            d.mkdir(parents=True, exist_ok=True)
            self.store[name] = (params, epoch)
            tmp = d / ".manifest.tmp"
            tmp.write_text(json.dumps({"epoch": epoch}))
            os.replace(tmp, d / self.MANIFEST)  # the atomic publish

        def exists(self, name):
            return name in self.store

        def path(self, name):
            return str(ckpt_root / name)

        def latest_valid_name(self):
            return None

        def restore(self, name, target_state, params_only=False):
            params, epoch = self.store[name]
            return StubState(params), epoch

    import distributed_training_pytorch_tpu.checkpoint.manager as mgr_mod

    manager = StubManager()
    p1 = _linear_params(seed=11)
    manager.commit("best", p1, epoch=1)

    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2))
    real_manifest = mgr_mod.MANIFEST_NAME
    try:
        mgr_mod.MANIFEST_NAME = StubManager.MANIFEST
        with InferenceServer(
            eng,
            batcher=MicroBatcher(buckets=(1, 2), max_delay_s=0.002),
            run_dir=str(run_dir),
            manager=manager,
            target_state=object(),
            serve_name="best",
            swap_poll_s=0.05,
            process_index=0,
        ) as server:
            server.start()
            x = [[1.0, 2.0, 3.0, 4.0]]

            def wait_version(v, timeout=5.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if eng.params_version == v:
                        return True
                    time.sleep(0.02)
                return False

            assert wait_version("best@e1"), "initial swap from manifest"
            code, before = _post(server.port, {"inputs": x})
            assert code == 200 and before["params_version"] == "best@e1"

            # Re-commit the SAME params at the same epoch: the identity
            # (mtime) changes, the swap fires, the bytes must not.
            time.sleep(0.05)
            manager.commit("best", {k: v.copy() for k, v in p1.items()}, epoch=1)
            deadline = time.monotonic() + 5.0
            while eng.swap_count < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert eng.swap_count >= 2  # initial manifest swap + re-commit
            code, again = _post(server.port, {"inputs": x})
            assert code == 200 and again == before  # bit-identical

            # A genuinely new checkpoint changes the served answer.
            manager.commit("best", _linear_params(seed=12), epoch=2)
            assert wait_version("best@e2")
            code, after = _post(server.port, {"inputs": x})
            assert code == 200
            assert after["params_version"] == "best@e2"
            assert after["outputs"] != before["outputs"]
    finally:
        mgr_mod.MANIFEST_NAME = real_manifest

    swaps = [r for r in _read_events(str(run_dir)) if r["event"] == "hot_swap"]
    assert len(swaps) >= 2
    assert swaps[0]["checkpoint"] == "best"
    assert swaps[-1]["to_version"] == "best@e2"


def test_preloaded_candidate_skips_startup_swap(tmp_path, tp_mesh):
    """An engine already serving the candidate checkpoint (restored before
    ``start()``) is not redundantly re-restored by the watcher's first
    poll, and no spurious startup ``hot_swap`` lands in the recorder; a
    later re-commit still swaps."""
    from distributed_training_pytorch_tpu.checkpoint.manager import MANIFEST_NAME

    ckpt = tmp_path / "weights" / "best"
    ckpt.mkdir(parents=True)
    manifest = ckpt / MANIFEST_NAME
    manifest.write_text(json.dumps({"epoch": 1}))

    class Mgr:
        def exists(self, name):
            return name == "best"

        def path(self, name):
            return str(ckpt)

        def latest_valid_name(self):
            return "best"

        def restore(self, name, target_state, params_only=False):
            return types.SimpleNamespace(params=_linear_params(seed=11)), 2

    run_dir = tmp_path / "run"
    eng = InferEngine(_linear_apply, tp_mesh, buckets=(1, 2))
    eng.swap_params(_linear_params(seed=11), version="best@e1")  # preloaded
    with InferenceServer(
        eng,
        batcher=MicroBatcher(buckets=(1, 2)),
        run_dir=str(run_dir),
        manager=Mgr(),
        target_state=object(),
        serve_name="best",
        swap_poll_s=0.05,
        process_index=0,
    ) as server:
        server.start()
        time.sleep(0.3)  # several watcher polls
        assert eng.swap_count == 1  # only the preload — no startup re-swap
        assert eng.params_version == "best@e1"
        # A real re-commit (manifest mtime changes) still fires the swap.
        os.utime(manifest, (time.time() + 5, time.time() + 5))
        deadline = time.monotonic() + 5.0
        while eng.swap_count < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.swap_count == 2 and eng.params_version == "best@e2"
    swaps = [r for r in _read_events(str(run_dir)) if r["event"] == "hot_swap"]
    assert len(swaps) == 1  # the re-commit only; no spurious startup record
    assert swaps[0]["from_version"] == "best@e1"
    assert swaps[0]["to_version"] == "best@e2"


# ---------------------------------------------------------------------------
# Monitor: synthetic server logs (no server process needed).


def _write_serve_log(run_dir, pulses):
    os.makedirs(os.path.dirname(resolve_events_path(run_dir)), exist_ok=True)
    now = time.time()
    recs = [
        {"event": "serve_start", "t_wall": now - 2.0, "attempt": 1, "port": 1234}
    ]
    for p in pulses:
        recs.append({"event": "request_batch", "t_wall": now, "attempt": 1, **p})
    with open(resolve_events_path(run_dir), "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_monitor_serve_slo_breach_exit_code(tmp_path):
    run = str(tmp_path / "srv")
    _write_serve_log(
        run,
        [{"qps": 120.0, "p50_ms": 3.0, "p99_ms": 45.0, "slo_p99_ms": 20.0,
          "slo_ok": False, "params_version": "best@e3", "rejected_total": 0}],
    )
    st = RunMonitor(run, AlertConfig(stale_after_s=60.0)).poll()
    assert st.kind == "serve" and st.status == "serving"
    assert st.verdict == "slo_breach"
    assert st.exit_code == 1  # the --once CI contract honors a server SLO
    assert "slo_breach" in st.active_alerts
    row = st.fleet_row()
    assert row["qps"] == "120.00" and row["p99"] == "45.0"
    assert st.serve["params_version"] == "best@e3"


def test_monitor_serve_healthy_and_trainer_row_shape(tmp_path):
    run = str(tmp_path / "srv_ok")
    _write_serve_log(
        run,
        [{"qps": 10.0, "p50_ms": 1.0, "p99_ms": 2.0, "slo_p99_ms": 20.0,
          "slo_ok": True, "params_version": "best@e1", "rejected_total": 0}],
    )
    st = RunMonitor(run, AlertConfig(stale_after_s=60.0)).poll()
    assert st.verdict == "healthy" and st.exit_code == 0
    # A trainer's row carries the same schema with serving columns blanked:
    train_run = str(tmp_path / "trn")
    os.makedirs(os.path.dirname(resolve_events_path(train_run)), exist_ok=True)
    with open(resolve_events_path(train_run), "w") as f:
        f.write(json.dumps({"event": "run_start", "t_wall": time.time(),
                            "attempt": 1}) + "\n")
    trow = RunMonitor(train_run, AlertConfig()).poll().fleet_row()
    srow = st.fleet_row()
    assert set(trow) == set(srow)  # one table renders both
    assert trow["qps"] == "-" and trow["p99"] == "-"


# ---------------------------------------------------------------------------
# Fleet controller: the mixed-fleet offer_chip advisory.


def test_offer_chip_in_action_vocabulary():
    from distributed_training_pytorch_tpu.telemetry.controller import (
        ACTION_KINDS,
        Action,
    )

    assert "offer_chip" in ACTION_KINDS
    a = Action(kind="offer_chip", reason="straggler")
    assert not a.respawns  # advisory: never consumes the restart budget


def test_fleet_controller_offers_freed_chip_to_serving_replica(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import fleet_controller as fc
    from distributed_training_pytorch_tpu.telemetry.controller import (
        Action,
        ControllerConfig,
    )
    from distributed_training_pytorch_tpu.telemetry.events import EventLog

    events_path = str(tmp_path / "ops.jsonl")
    trainer = fc.RunSpec(
        name="trainer0", run_dir=str(tmp_path / "trainer0"),
        adopt=True, device_ids=(0, 1), mesh="fsdp2",
    )
    server = fc.RunSpec(
        name="server0", run_dir=str(tmp_path / "server0"),
        kind="serve", adopt=True,
    )
    fleet = fc.FleetController(
        [trainer, server],
        config=ControllerConfig(max_restarts=3),
        monitor_config=AlertConfig(),
        event_log=EventLog(events_path, process_index=0),
        interval=0.1,
    )
    action = Action(
        kind="restart_excluding",
        reason="straggler",
        params={"exclude_chip": 1},
        evidence=[{"metric": "straggler_ratio", "value": 3.2}],
    )
    status = types.SimpleNamespace(attempt=2, status="training",
                                   verdict="straggler")
    fleet._offer_freed_chip(fleet.runs["trainer0"], action, status)
    fleet.events.close()

    with open(events_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    offers = [r for r in recs if r.get("action") == "offer_chip"]
    assert len(offers) == 1  # one per serving replica, none to the trainer
    offer = offers[0]
    assert offer["run"] == "server0"
    assert offer["params"] == {
        "chip": 1, "from_run": "trainer0", "to_run": "server0",
    }
    assert offer["reason"] == "straggler"
    assert offer["evidence"]  # the triggering evidence rides along
    assert fleet.runs["server0"].actions[0].kind == "offer_chip"


# ---------------------------------------------------------------------------
# Import neutrality: serving pulls no jax at package import.


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_serving_package_import_is_neutral():
    """The acceptance neutrality pillar: a trainer that imports serving
    but never uses it cannot perturb training. The package import loads
    ONLY the pure-Python batcher — no engine, no server, no jax device or
    PRNG touch — so it can change neither params nor trace_counts of a
    run that ignores it. (The parent package imports jax on its own;
    neutrality is about what importing ``serving`` ADDS.)"""
    code = (
        "import sys\n"
        "import distributed_training_pytorch_tpu  # parent may pull jax itself\n"
        "before = set(sys.modules)\n"
        "import distributed_training_pytorch_tpu.serving as s\n"
        "added = set(sys.modules) - before\n"
        "pkg = 'distributed_training_pytorch_tpu.serving'\n"
        "extra = {m for m in added if not m.startswith(pkg)}\n"
        "assert not extra, f'serving import pulled foreign modules: {extra}'\n"
        "assert pkg + '.engine' not in added, 'engine (jax) loaded eagerly'\n"
        "assert pkg + '.server' not in added, 'server loaded eagerly'\n"
        "assert s.MicroBatcher and s.pick_bucket\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr + out.stdout
    assert "ok" in out.stdout
    # And the batcher module itself is statically jax-free.
    src = open(os.path.join(
        REPO, "distributed_training_pytorch_tpu", "serving", "batcher.py"
    )).read()
    assert "import jax" not in src
