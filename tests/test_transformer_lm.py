"""TransformerLM (models/transformer_lm.py): causality, attention impls,
MoE blocks, engine integration, ring-attention sequence parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_pytorch_tpu.models import LMTiny
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import TrainEngine


def tokens_batch(b, t, vocab=256, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, vocab, size=(b, t)), jnp.int32)


def test_forward_shape_and_dtype():
    model = LMTiny()
    toks = tokens_batch(2, 16)
    variables = model.init(jax.random.key(0), toks)
    logits = model.apply(variables, toks)
    assert logits.shape == (2, 16, 256)
    assert logits.dtype == jnp.float32


def test_causality():
    """Changing suffix tokens must not change prefix logits."""
    model = LMTiny()
    toks = tokens_batch(1, 20, seed=1)
    variables = model.init(jax.random.key(0), toks)
    base = model.apply(variables, toks)
    perturbed = toks.at[0, 12:].set((toks[0, 12:] + 7) % 256)
    out = model.apply(variables, perturbed)
    np.testing.assert_allclose(
        np.asarray(base[0, :12]), np.asarray(out[0, :12]), atol=1e-5
    )
    assert not np.allclose(np.asarray(base[0, 12:]), np.asarray(out[0, 12:]))


def test_flash_impl_matches_plain():
    """Forced Pallas kernel (interpreter on CPU) agrees with the plain path."""
    toks = tokens_batch(1, 24, seed=2)
    plain = LMTiny(attention_impl="plain")
    variables = plain.init(jax.random.key(0), toks)
    flash = LMTiny(attention_impl="flash")
    np.testing.assert_allclose(
        np.asarray(flash.apply(variables, toks)),
        np.asarray(plain.apply(variables, toks)),
        atol=2e-4,
    )


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_moe_blocks_present_and_finite():
    model = LMTiny(moe_every=2, num_experts=4)
    toks = tokens_batch(2, 8, seed=3)
    variables = model.init(jax.random.key(0), toks)
    # block 1 (index 1, 1-indexed 2) is MoE; block 0 dense.
    params = variables["params"]
    assert "moe" in params["DecoderBlock_1"]
    assert "mlp_in" in params["DecoderBlock_0"]
    logits = model.apply(variables, toks)
    assert np.isfinite(np.asarray(logits)).all()


def test_lm_overfits_with_engine(devices):
    """End-to-end: next-token objective through TrainEngine on the data mesh;
    loss decreases on a tiny repeated corpus."""
    mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)
    model = LMTiny(vocab_size=64)

    def criterion(logits, batch):
        targets = batch["label"]  # next tokens [B, T]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
        return loss, {"loss": loss}

    def loss_fn(params, model_state, batch, rng, train):
        logits = model.apply({"params": params}, batch["image"], train=train,
                             rngs={"dropout": rng} if train else None)
        loss, metrics = criterion(logits, batch)
        return loss, (metrics, model_state)

    engine = TrainEngine(loss_fn, optax.adam(1e-2), mesh)
    rng = np.random.RandomState(4)
    seq = rng.randint(0, 64, size=(16, 17)).astype(np.int32)
    batch = engine.shard_batch({"image": seq[:, :-1], "label": seq[:, 1:]})
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 16), jnp.int32))
    )
    losses = []
    for _ in range(30):
        state, m = engine.train_step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses


@pytest.mark.slow
def test_ring_attention_impl_matches_plain(devices):
    """attention_impl='ring' over a seq mesh matches the plain causal path."""
    mesh = mesh_lib.create_mesh({mesh_lib.SEQ_AXIS: 4}, devices=devices[:4])
    toks = tokens_batch(2, 32, seed=5)
    plain = LMTiny(attention_impl="plain")
    variables = plain.init(jax.random.key(0), toks)
    ring = LMTiny(attention_impl="ring", mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(ring.apply(variables, toks)),
        np.asarray(plain.apply(variables, toks)),
        atol=2e-4,
    )


def test_gpt_small_factory_accepts_max_len_override():
    """Regression: GPTSmall(max_len=...) must not collide with its default
    (eval_shape only — the 124M-param model never materializes)."""
    from distributed_training_pytorch_tpu.models import GPTSmall

    model = GPTSmall(vocab_size=1000, max_len=256)
    toks = jnp.zeros((1, 256), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)
    assert shapes["params"]["pos_embed"].shape == (1, 256, 768)
    logits = jax.eval_shape(
        model.apply, shapes, jnp.zeros((2, 64), jnp.int32)
    )
    assert logits.shape == (2, 64, 1000)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_cached_decode_matches_full_forward():
    """Single-token KV-cache decode produces the same logits as the full
    causal forward at every position."""
    model = LMTiny(vocab_size=32, max_len=16)
    toks = tokens_batch(2, 10, vocab=32, seed=6)
    variables = model.init(jax.random.key(0), toks)
    full = model.apply(variables, toks)  # [B, T, V]

    cache = None
    step_logits = []
    for t in range(10):
        inputs = {**variables} if cache is None else {**variables, "cache": cache}
        logits, state = model.apply(inputs, toks[:, t : t + 1], decode=True, mutable=["cache"])
        cache = state["cache"]
        step_logits.append(logits[:, 0])
    stepped = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(stepped), np.asarray(full), atol=2e-4)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_generate_greedy_continues_prompt():
    from distributed_training_pytorch_tpu.models.transformer_lm import generate

    model = LMTiny(vocab_size=32, max_len=24)
    prompt = tokens_batch(2, 6, vocab=32, seed=7)
    variables = model.init(jax.random.key(0), prompt)
    out = generate(model, variables, prompt, num_steps=8, rng=jax.random.key(1))
    assert out.shape == (2, 14)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(prompt))
    # Greedy continuation must equal argmax of the full forward at each step.
    full = model.apply(variables, out[:, :-1])
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, 5:], axis=-1)), np.asarray(out[:, 6:])
    )


def test_generate_sampling_is_seeded():
    from distributed_training_pytorch_tpu.models.transformer_lm import generate

    model = LMTiny(vocab_size=32, max_len=24)
    prompt = tokens_batch(1, 4, vocab=32, seed=8)
    variables = model.init(jax.random.key(0), prompt)
    a = generate(model, variables, prompt, 8, jax.random.key(5), temperature=1.0)
    b = generate(model, variables, prompt, 8, jax.random.key(5), temperature=1.0)
    c = generate(model, variables, prompt, 8, jax.random.key(6), temperature=1.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_return_hidden_matches_logits_projection():
    """hidden @ E^T == the model's own logits (the fused-CE contract)."""
    model = LMTiny(vocab_size=32, max_len=16)
    toks = tokens_batch(2, 8, vocab=32, seed=9)
    variables = model.init(jax.random.key(0), toks)
    logits = model.apply(variables, toks)
    hidden = model.apply(variables, toks, return_hidden=True)
    emb = variables["params"]["embed"]["embedding"]
    recon = hidden.astype(jnp.float32) @ emb.T.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(recon), np.asarray(logits), atol=1e-5)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_fused_loss_includes_moe_aux(devices):
    """MoE LM through the fused loss: router aux losses join the objective and
    the engine step runs with finite metrics."""
    from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss

    mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)
    model = LMTiny(vocab_size=64, moe_every=2, num_experts=4)
    engine = TrainEngine(make_fused_lm_loss(model), optax.adam(1e-3), mesh)
    rng = np.random.RandomState(13)
    seq = rng.randint(0, 64, size=(16, 17)).astype(np.int32)
    batch = engine.shard_batch({"image": seq[:, :-1], "label": seq[:, 1:]})
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 16), jnp.int32))
    )
    state, m = engine.train_step(state, batch)
    assert float(m["moe_load_balance"]) >= 1.0 - 1e-5  # >= 1 by Cauchy-Schwarz
    assert np.isfinite(float(m["moe_router_z"]))
    assert float(m["loss"]) > float(m["nll"])  # aux terms actually added


@pytest.mark.slow
def test_moe_lm_cached_decode_and_generate():
    """KV-cache decode works through MoE blocks: with capacity headroom the
    training-time router drops nothing, so the capacity-free decode router
    produces the same logits as the full causal forward; generate() runs."""
    from distributed_training_pytorch_tpu.models.transformer_lm import (
        TransformerLM,
        generate,
    )

    model = TransformerLM(
        vocab_size=32, hidden_dim=16, depth=2, num_heads=2, mlp_dim=32,
        max_len=16, moe_every=2, num_experts=4, moe_capacity_factor=16.0,
        attention_impl="plain",
    )
    toks = tokens_batch(2, 6, vocab=32, seed=21)
    variables = model.init(jax.random.key(0), toks)
    full = model.apply(variables, toks)

    cache = None
    step_logits = []
    for t in range(6):
        inputs = {**variables} if cache is None else {**variables, "cache": cache}
        logits, state = model.apply(
            inputs, toks[:, t : t + 1], decode=True, mutable=["cache"]
        )
        cache = state["cache"]
        step_logits.append(logits[:, 0])
    stepped = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(stepped), np.asarray(full), atol=2e-4)

    out = generate(model, variables, toks, num_steps=5, rng=jax.random.key(1))
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(toks))
