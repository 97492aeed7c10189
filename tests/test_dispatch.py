"""ops/dispatch.py — the single kernel-policy decision point (ISSUE 17).

Covers the knob grammar (``PALLAS`` env parse, tri-state resolve), the
one-time ``kernel_dispatch`` recording contract (dedup, buffer-then-flush
into an event sink), the per-model routing, and the two acceptance
invariants: the OFF path reproduces the historical program bit-exactly
(params AND outputs), and toggling the kernel knob recompiles exactly once
per shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.ops import dispatch


@pytest.fixture(autouse=True)
def _fresh_dispatch():
    dispatch.reset()
    yield
    dispatch.reset()


# ---------------------------------------------------------------------------
# knob grammar
# ---------------------------------------------------------------------------


def test_pallas_from_env_parse():
    assert dispatch.pallas_from_env({"PALLAS": "1"}) is True
    assert dispatch.pallas_from_env({"PALLAS": "0"}) is False
    assert dispatch.pallas_from_env({}) is None
    assert dispatch.pallas_from_env({"PALLAS": ""}) is None
    assert dispatch.pallas_from_env({}, default=True) is True
    with pytest.raises(ValueError):
        dispatch.pallas_from_env({"PALLAS": "yes"})


def test_resolve_tri_state():
    assert dispatch.resolve(True, False) is True
    assert dispatch.resolve(False, True) is False
    assert dispatch.resolve(None, "legacy") == "legacy"


# ---------------------------------------------------------------------------
# one-time recording + sink
# ---------------------------------------------------------------------------


def test_record_dedups_per_process():
    assert dispatch.record("m", "op", "plain", reason="r") is True
    assert dispatch.record("m", "op", "plain", reason="r") is False
    assert dispatch.record("m", "op", "pallas", reason="r") is True  # new path
    paths = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
    assert paths == {("m", "op", "plain"), ("m", "op", "pallas")}


def test_decisions_buffer_then_flush_into_the_sink():
    """Decisions made while building the model (before the Trainer installs
    EventLog.emit) must still land in the run's event log."""
    dispatch.record("m", "op", "plain", reason="before-sink", seq_len=7)
    got = []
    dispatch.set_event_sink(lambda event, **f: got.append((event, f)))
    assert [(e, f["reason"]) for e, f in got] == [
        ("kernel_dispatch", "before-sink")]
    assert got[0][1]["seq_len"] == 7
    dispatch.record("m", "op2", "flash", reason="live")
    assert [f["reason"] for _, f in got] == ["before-sink", "live"]
    # dedup state survives sink teardown (one-time per process, not per run)
    dispatch.clear_event_sink()
    assert dispatch.record("m", "op2", "flash", reason="live") is False


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------


def test_attention_fn_routing_on_cpu():
    # explicit off: plain, named
    assert dispatch.attention_fn("vit", False) is None
    # auto on a non-TPU backend: plain, named with the backend
    assert dispatch.attention_fn("vit", None) is None
    reasons = {r["reason"] for r in dispatch.records()}
    assert "pallas=False" in reasons
    assert any(r.startswith("auto: backend=") for r in reasons)
    # forced on: a callable that records the flash path per actual length
    fn = dispatch.attention_fn("vit", True)
    assert fn is not None
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32) for _ in range(3))
    out = fn(q, k, v)
    assert out.shape == q.shape
    flash = [r for r in dispatch.records() if r["path"] == "flash"]
    assert flash and flash[0]["reason"] == "pallas=True (forced)"
    assert flash[0]["seq_len"] == 8


def test_attention_fn_names_the_short_sequence_fall_through(monkeypatch):
    """The formerly-silent fall-through: auto mode below FLASH_MIN_SEQ_LEN
    routes to plain — same routing as ever, now with a named record.
    Backend pinned to 'tpu' so auto mode builds the thresholded adapter; the
    short sequence then takes make_attention_fn's plain branch (CPU-safe)."""
    from distributed_training_pytorch_tpu.ops.pallas import FLASH_MIN_SEQ_LEN

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = dispatch.attention_fn("vit", None)
    assert fn is not None
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 8, 2, 4), jnp.float32) for _ in range(3))
    out = fn(q, k, v)
    assert out.shape == q.shape
    entry = [r for r in dispatch.records() if r.get("seq_len") == 8][0]
    assert entry["path"] == "plain"
    assert entry["reason"] == f"T=8 < FLASH_MIN_SEQ_LEN={FLASH_MIN_SEQ_LEN}"


def test_lm_attention_impl_mapping():
    assert dispatch.lm_attention_impl("auto", True) == "flash"
    assert dispatch.lm_attention_impl("auto", False) == "plain"
    assert dispatch.lm_attention_impl("auto", None) == "auto"
    assert dispatch.lm_attention_impl("ring", None) == "ring"


def test_conv1x1_policy_auto_stays_off_and_is_named():
    assert dispatch.conv1x1_policy("resnet", None) is False
    assert dispatch.conv1x1_policy("resnet", True) is True
    assert dispatch.conv1x1_policy("resnet", False, legacy=True) is False
    assert dispatch.conv1x1_policy("resnet", None, legacy=True) is True
    by_reason = {r["reason"]: r["path"] for r in dispatch.records()}
    assert by_reason["pallas=True"] == "pallas"
    assert by_reason["pallas=False"] == "plain"
    assert by_reason["legacy knob"] == "pallas"
    assert any("opt in" in r or "auto" in r for r in by_reason)


@pytest.mark.slow  # soak-shaped: moved out of tier-1 to keep it inside its cap (PR 21)
def test_model_builds_record_their_resolutions():
    from distributed_training_pytorch_tpu.models import ConvNeXtTiny, ResNet18Slim

    x = jnp.ones((1, 16, 16, 3), jnp.float32)
    ResNet18Slim(num_classes=4).init(jax.random.key(0), x)
    ConvNeXtTiny(num_classes=4).init(jax.random.key(0), x)
    seen = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
    assert ("resnet", "conv1x1_bn_act", "plain") in seen
    assert ("convnext", "dense_gelu", "plain") in seen


def test_vgg_records_the_no_coverage_no_op():
    from distributed_training_pytorch_tpu.models import create_model

    create_model("vgg16", 4, pallas=True)
    seen = [r for r in dispatch.records() if r["model"] == "vgg16"]
    assert seen and seen[0]["path"] == "plain"
    assert "no fused-kernel coverage" in seen[0]["reason"]


# ---------------------------------------------------------------------------
# acceptance invariants: OFF is bit-exact; toggling recompiles once per shape
# ---------------------------------------------------------------------------


def _bit_equal_trees(a, b):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for la, lb in zip(flat_a, flat_b, strict=True):
        assert la.dtype == lb.dtype and la.shape == lb.shape
        assert np.array_equal(np.asarray(la), np.asarray(lb)), "bit drift"


@pytest.mark.parametrize("factory", [
    # the two conv zoo builds are the expensive ones: tier-1 keeps vit (PR 21)
    pytest.param("resnet", marks=pytest.mark.slow),
    pytest.param("convnext", marks=pytest.mark.slow),
    "vit",
])
def test_pallas_off_reproduces_the_historical_program_bit_exactly(factory):
    """pallas=False and the unset default produce bit-identical params AND
    outputs — PALLAS=0 is the historical program, not a near miss."""
    from distributed_training_pytorch_tpu.models import (
        ConvNeXtTiny,
        ResNet18Slim,
        ViTTiny,
    )

    make = {"resnet": ResNet18Slim, "convnext": ConvNeXtTiny, "vit": ViTTiny}[factory]
    x = jnp.linspace(0, 1, 1 * 16 * 16 * 3, dtype=jnp.float32).reshape(1, 16, 16, 3)
    legacy = make(num_classes=4)
    off = make(num_classes=4, pallas=False)
    v_legacy = legacy.init(jax.random.key(0), x)
    v_off = off.init(jax.random.key(0), x)
    _bit_equal_trees(v_legacy, v_off)
    out_legacy = legacy.apply(v_legacy, x)
    out_off = off.apply(v_off, x)
    assert np.array_equal(np.asarray(out_legacy), np.asarray(out_off))


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_convnext_pallas_param_tree_is_knob_invariant():
    """Flipping the ConvNeXt kernel knob changes the program, never the
    param tree: bit-identical init (PallasDenseAct pins nn.Dense's names,
    shapes, and initializers), near-identical forward."""
    from distributed_training_pytorch_tpu.models import ConvNeXtTiny

    x = jnp.linspace(-1, 1, 2 * 16 * 16 * 3, dtype=jnp.float32).reshape(2, 16, 16, 3)
    plain = ConvNeXtTiny(num_classes=4, pallas=False)
    fused = ConvNeXtTiny(num_classes=4, pallas=True)
    v_plain = plain.init(jax.random.key(0), x)
    v_fused = fused.init(jax.random.key(0), x)
    _bit_equal_trees(v_plain, v_fused)  # same tree -> checkpoints interchange
    np.testing.assert_allclose(
        np.asarray(fused.apply(v_plain, x)),
        np.asarray(plain.apply(v_plain, x)),
        atol=2e-5,
    )


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_toggling_the_kernel_knob_recompiles_exactly_once_per_shape():
    """trace_counts contract: each knob setting is one program — repeated
    calls at a shape never retrace, a new shape traces exactly once more."""
    from distributed_training_pytorch_tpu.models import ConvNeXtTiny

    x1 = jnp.ones((1, 16, 16, 3), jnp.float32)
    x2 = jnp.ones((2, 16, 16, 3), jnp.float32)
    variables = ConvNeXtTiny(num_classes=4, pallas=False).init(jax.random.key(0), x1)
    for knob in (False, True):
        model = ConvNeXtTiny(num_classes=4, pallas=knob)
        count = [0]

        def fn(v, x, model=model, count=count):
            count[0] += 1
            return model.apply(v, x)

        jfn = jax.jit(fn)
        jfn(variables, x1), jfn(variables, x1)
        assert count[0] == 1, f"pallas={knob}: retrace at a seen shape"
        jfn(variables, x2), jfn(variables, x2)
        assert count[0] == 2, f"pallas={knob}: new shape must trace once"
