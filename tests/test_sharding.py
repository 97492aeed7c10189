"""Sharded training end-to-end (ISSUE 10): FSDP/TP meshes in the real
Trainer/TrainEngine hot path.

The acceptance pillars, each test-enforced here (the heavyweight
kill/resume + full-trainer parity legs live in ``scripts/sharding_smoke.py``
— verify.sh stage 7 — so the tier-1 suite stays fast):

* **Mesh parity** — an ``fsdp=8`` engine run is BIT-EXACT with pure DP
  (losses and params; the batch stays 8-way sharded so every reduction has
  the same participant order), and a sharded INIT reproduces the
  replicated init bit-for-bit (``jax_threefry_partitionable``, jax 0.9's
  default).
* **Chained windows on sharded state** — bit-exact with sharded
  single-step execution, one compile per shape (the PR-2 invariants
  extended to SPMD).
* **Resharding checkpoints** — a checkpoint written under one mesh
  restores under another (DP <-> FSDP both directions) value-exact, with
  the sharding-metadata record in meta and a ``checkpoint_reshard`` event.
* **Historical program** — a pure-DP mesh with the sharding knobs at their
  defaults lowers the byte-identical program the pre-sharding engine did.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_training_pytorch_tpu.checkpoint.manager import CheckpointManager
from distributed_training_pytorch_tpu.models.vit import ViTTiny
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.parallel import sharding as sharding_lib
from distributed_training_pytorch_tpu.parallel import transformer_tp_rules
from distributed_training_pytorch_tpu.telemetry import mfu as mfu_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss


def criterion(logits, batch):
    loss = cross_entropy_loss(logits, batch["label"])
    return loss, {"loss": loss}


def make_vit_engine(mesh, rules=None, fsdp_min_size=1024):
    model = ViTTiny(num_classes=4)
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh,
        sharding_rules=rules,
        fsdp_min_size=fsdp_min_size,
    )
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 16, 16, 3)))
    )
    return engine, state


def host_batch(seed=0, n=16):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.randn(n, 16, 16, 3).astype(np.float32),
        "label": rng.randint(0, 4, size=(n,)).astype(np.int32),
    }


def trees_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            jax.tree.leaves(jax.device_get(a)),
            jax.tree.leaves(jax.device_get(b)),
            strict=True,
        )
    )


# ---------------------------------------------------------------------------
# Mesh-spec grammar + batch-shard extent (the shared MESH/BENCH_MESH knob).


def test_mesh_config_from_spec_grammar():
    assert mesh_lib.mesh_config_from_spec("dp8") == mesh_lib.MeshConfig(data=8)
    assert mesh_lib.mesh_config_from_spec("fsdp4x2") == mesh_lib.MeshConfig(
        data=2, fsdp=4
    )
    assert mesh_lib.mesh_config_from_spec("tp2x4") == mesh_lib.MeshConfig(
        data=4, tensor=2
    )
    assert mesh_lib.mesh_config_from_spec("dp2fsdp2tp2") == mesh_lib.MeshConfig(
        data=2, fsdp=2, tensor=2
    )
    assert mesh_lib.mesh_config_from_spec("fsdp8") == mesh_lib.MeshConfig(
        data=1, fsdp=8
    )


@pytest.mark.parametrize("bad", ["", "bogus3", "dp2dp4", "fsdp2y4", "8dp"])
def test_mesh_config_from_spec_rejects(bad):
    with pytest.raises(ValueError):
        mesh_lib.mesh_config_from_spec(bad)


def test_batch_shard_extent(devices):
    assert mesh_lib.batch_shard_extent(mesh_lib.create_mesh({"data": 8})) == 8
    assert (
        mesh_lib.batch_shard_extent(
            mesh_lib.create_mesh({"data": 2, "fsdp": 2, "tensor": 2})
        )
        == 4
    )
    assert (
        mesh_lib.batch_shard_extent(mesh_lib.create_mesh({"data": 2, "tensor": 4}))
        == 2
    )


def test_throughput_fields_divide_by_batch_replicas(devices):
    mesh = mesh_lib.create_mesh({"data": 2, "tensor": 4})
    fields = mfu_lib.throughput_fields(800.0, mesh)
    assert fields["items_per_sec_chip"] == 100.0  # 8 devices
    assert fields["items_per_sec_replica"] == 400.0  # 2 batch replicas
    assert fields["batch_replicas"] == 2


# ---------------------------------------------------------------------------
# Shard-byte accounting + the checkpoint sharding record.


def test_sharding_record_and_shard_bytes(devices):
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    tree = {
        "kernel": jax.device_put(
            np.ones((48, 512), np.float32), NamedSharding(mesh, P(None, "fsdp"))
        ),
        "bias": jax.device_put(np.ones((32,), np.float32), NamedSharding(mesh, P())),
    }
    record = sharding_lib.sharding_record(tree)
    assert record["mesh"] == {"data": 2, "fsdp": 4}
    assert list(record["specs"].values()) == [str(P(None, "fsdp"))]
    # replicated-only trees carry no record (pre-sharding compatibility)
    assert (
        sharding_lib.sharding_record(
            {"b": jax.device_put(np.ones(4, np.float32), NamedSharding(mesh, P()))}
        )
        is None
    )
    # per-device bytes from the leaves' own shardings
    assert sharding_lib.tree_shard_bytes(tree) == 48 * 512 * 4 / 4 + 32 * 4


# ---------------------------------------------------------------------------
# Engine parity + sharded init (the fast acceptance core; the full-model
# trainer legs live in scripts/sharding_smoke.py).


@pytest.fixture(scope="module")
def parity_runs(devices):
    def run(mesh, rules=None):
        engine, state = make_vit_engine(mesh, rules)
        init_params = jax.device_get(state.params)
        losses = []
        for i in range(3):
            batch = engine.shard_batch(host_batch(seed=i))
            state, m = engine.train_step(state, batch)
            losses.append(float(m["loss"]))
        return engine, state, losses, init_params

    dp = run(mesh_lib.create_mesh({"data": 8}))
    fsdp8 = run(mesh_lib.MeshConfig(data=1, fsdp=8).build())
    mixed = run(
        mesh_lib.MeshConfig(data=2, fsdp=2, tensor=2).build(),
        rules=transformer_tp_rules(),
    )
    return {"dp": dp, "fsdp8": fsdp8, "mixed": mixed}


def test_fsdp_mesh_bit_exact_with_dp(parity_runs):
    _, dp_state, dp_losses, _ = parity_runs["dp"]
    engine, state, losses, _ = parity_runs["fsdp8"]
    assert losses == dp_losses  # bit-exact, not allclose
    assert trees_equal(state.params, dp_state.params)
    assert trees_equal(state.opt_state, dp_state.opt_state)
    specs = [str(leaf.sharding.spec) for leaf in jax.tree.leaves(state.params)]
    assert any("fsdp" in s for s in specs), specs


def test_sharded_init_bit_exact_with_replicated(parity_runs):
    # Sharded init (init_state jitted with sharded out_shardings — no
    # replicate-then-reshard step) must produce the same numbers the
    # replicated init does: threefry partitionable makes per-shard key
    # streams location-invariant.
    _, _, _, dp_init = parity_runs["dp"]
    for name in ("fsdp8", "mixed"):
        _, _, _, init = parity_runs[name]
        assert trees_equal(init, dp_init), name


def test_tp_mesh_matches_dp_to_float_ulp(parity_runs):
    # TP contraction splits + 4-way batch shards legally regroup float
    # sums: first step is bit-exact, the trajectory tracks DP at f32 ULP.
    _, _, dp_losses, _ = parity_runs["dp"]
    engine, state, losses, _ = parity_runs["mixed"]
    assert losses[0] == dp_losses[0]
    np.testing.assert_allclose(losses, dp_losses, rtol=0, atol=5e-6)
    specs = {
        jax.tree_util.keystr(p): str(leaf.sharding.spec)
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    assert any("tensor" in s for s in specs.values()), specs
    assert any("fsdp" in s for s in specs.values()), specs


def test_chained_window_bit_exact_on_sharded_state(parity_runs, devices):
    """PR-2's chained ≡ sequential invariant on genuinely sharded state:
    one chained window of 3 steps == 3 single steps, bit-exact, compiled
    exactly once."""
    mesh = mesh_lib.MeshConfig(data=1, fsdp=8).build()
    engine, state = make_vit_engine(mesh)
    batches = [host_batch(seed=10 + i) for i in range(3)]
    seq_state = state
    for hb in batches:
        seq_state, _ = engine.train_step(seq_state, engine.shard_batch(hb))

    chained_engine, chained_state = make_vit_engine(mesh)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    window = mesh_lib.global_chain_array_from_host_local(stacked, mesh)
    chained_state, metrics = chained_engine.train_steps_chained(
        chained_state, window, 3
    )
    assert trees_equal(chained_state.params, seq_state.params)
    assert trees_equal(chained_state.opt_state, seq_state.opt_state)
    assert chained_engine.trace_counts["chained_3"] == 1
    assert jax.tree.leaves(metrics)[0].shape[0] == 3  # per-step scan outputs


def test_chained_prefetch_window_shards_batch_axis(devices):
    """device_prefetch_chained's staging layout on an fsdp mesh: the
    leading (step) axis stays whole, the batch axis splits over data x
    fsdp — per-chip H2D bytes are global/extent, the tentpole's staging
    claim."""
    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 4})
    stacked = jax.tree.map(
        lambda *xs: np.stack(xs), *[host_batch(seed=i) for i in range(2)]
    )
    window = mesh_lib.global_chain_array_from_host_local(stacked, mesh)
    leaf = window["image"]
    assert leaf.shape == (2, 16, 16, 16, 3)
    shard = leaf.addressable_shards[0].data
    assert shard.shape == (2, 2, 16, 16, 3)  # batch 16 / (data*fsdp = 8)


# ---------------------------------------------------------------------------
# Resharding checkpoints.


@pytest.fixture(scope="module")
def reshard_states(parity_runs):
    return parity_runs["dp"][:2], parity_runs["fsdp8"][:2]


def test_checkpoint_reshards_both_directions(tmp_path, reshard_states):
    (dp_engine, dp_state), (f_engine, f_state) = reshard_states
    events = tmp_path / "events.jsonl"

    class Log:
        enabled = True

        def emit(self, event, **fields):
            with open(events, "a") as f:
                f.write(json.dumps({"event": event, **fields}) + "\n")

    mgr = CheckpointManager(os.fspath(tmp_path / "ckpt"))
    mgr.event_log = Log()
    # FSDP -> DP
    mgr.save("sharded", f_state, epoch=1)
    mgr.wait()
    meta = mgr.read_meta("sharded")
    assert meta["sharding"]["mesh"] == {"data": 1, "fsdp": 8}
    assert meta["sharding"]["specs"]  # non-replicated leaves recorded
    restored, _ = mgr.restore("sharded", dp_state)
    assert trees_equal(restored.params, f_state.params)
    assert all(
        "fsdp" not in str(leaf.sharding.spec)
        for leaf in jax.tree.leaves(restored.params)
    )
    # DP -> FSDP
    mgr.save("replicated", dp_state, epoch=1)
    mgr.wait()
    assert "sharding" not in mgr.read_meta("replicated")  # pure DP: no record
    restored_f, _ = mgr.restore("replicated", f_state)
    assert trees_equal(restored_f.params, dp_state.params)
    assert any(
        "fsdp" in str(leaf.sharding.spec)
        for leaf in jax.tree.leaves(restored_f.params)
    )
    recorded = [json.loads(line) for line in open(events)]
    reshard = [e for e in recorded if e["event"] == "checkpoint_reshard"]
    assert len(reshard) == 2
    assert reshard[0]["from_mesh"] == {"data": 1, "fsdp": 8}
    assert reshard[0]["to_mesh"] is None  # DP target carries no record


def test_async_saver_records_live_sharding(tmp_path, reshard_states):
    from distributed_training_pytorch_tpu.resilience import AsyncCheckpointSaver

    _, (f_engine, f_state) = reshard_states
    mgr = CheckpointManager(os.fspath(tmp_path / "async_ckpt"))
    with AsyncCheckpointSaver(mgr) as saver:
        saver.save_async("snap", f_state, epoch=2)
        saver.flush()
    meta = mgr.read_meta("snap")
    # the snapshot is host numpy — the record must have been captured from
    # the live sharded arrays before device_get stripped it
    assert meta["sharding"]["mesh"] == {"data": 1, "fsdp": 8}


# ---------------------------------------------------------------------------
# Historical-program parity (the PR-3/4/6/8 opt-in convention).


def test_pure_dp_default_program_byte_identical(devices):
    """A pure-DP engine with the sharding knobs untouched and one with an
    explicitly-empty rule list lower byte-identical programs: the sharding
    machinery is opt-in by MESH, and a data-only mesh reproduces the
    historical program exactly."""
    mesh = mesh_lib.create_mesh({"data": 8})
    default_engine, state = make_vit_engine(mesh, rules=None, fsdp_min_size=2**18)
    explicit_engine = TrainEngine(
        default_engine.loss_fn,
        default_engine.optimizer,
        mesh,
        sharding_rules=(),
        fsdp_min_size=2**18,
    )
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        host_batch(),
    )
    a = default_engine.lower_step_probe(state, batch, donate=True).as_text()
    b = explicit_engine.lower_step_probe(state, batch, donate=True).as_text()
    assert a == b


# ---------------------------------------------------------------------------
# Trainer surface: divisibility validation + auto rule resolution.


def test_trainer_rejects_indivisible_batch(tmp_path, devices):
    from test_trainer import ToyTrainer

    mesh = mesh_lib.create_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    with pytest.raises(ValueError, match="batch-shard extent"):
        ToyTrainer(
            max_epoch=1,
            batch_size=18,  # not divisible by data x fsdp = 4
            save_folder=os.fspath(tmp_path),
            mesh=mesh,
            progress=False,
            num_workers=0,
        )


def test_trainer_auto_rules_resolve_by_mesh(tmp_path, devices):
    from test_trainer import ToyTrainer

    # No full construction needed to test the hook's resolution rule:
    # build_sharding_rules reads only self.mesh.
    class Probe:
        pass

    probe = Probe()
    probe.mesh = mesh_lib.create_mesh({"data": 2, "tensor": 4})
    rules = ToyTrainer.build_sharding_rules(probe)
    assert rules and any("qkv" in pattern for pattern, _ in rules)
    probe.mesh = mesh_lib.create_mesh({"data": 8})
    assert ToyTrainer.build_sharding_rules(probe) is None


def test_tp_rules_cover_the_lm_naming(devices):
    """ISSUE 10: transformer_lm shards via transformer_tp_rules — its
    attn_out/mlp_in/mlp_out/embed naming must actually match (the ViT-only
    rule set silently left the LM replicated)."""
    from distributed_training_pytorch_tpu.models.transformer_lm import LMTiny

    mesh = mesh_lib.create_mesh({"data": 4, "tensor": 2})
    model = LMTiny(vocab_size=64)

    def lm_loss(params, model_state, batch, rng, train):
        logits = model.apply({"params": params}, batch["tokens"], train=train,
                             rngs={"dropout": rng} if train else None)
        loss = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), batch["labels"].reshape(-1)
        )
        return loss, ({"loss": loss}, model_state)

    engine = TrainEngine(
        lm_loss, optax.sgd(0.01), mesh,
        sharding_rules=transformer_tp_rules(), fsdp_min_size=2**30,
    )
    state = engine.init_state(
        jax.random.key(0),
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
    )
    specs = {
        jax.tree_util.keystr(p): str(leaf.sharding.spec)
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    tp_sharded = [k for k, s in specs.items() if "tensor" in s]
    assert any("qkv" in k for k in tp_sharded), specs
    assert any("attn_out" in k for k in tp_sharded), specs
    assert any("mlp_in" in k for k in tp_sharded), specs
    assert any("mlp_out" in k for k in tp_sharded), specs
    assert any("embed" in k for k in tp_sharded), specs
