"""Each plain reference against the repo's model at a tiny size: the same
weights (laid out by ``to_program``) and the same batch give the same loss
and the same gradient norms, in float32."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import traffic as traffic_lib
from benchmarks.reference import common, gpt2, vgg16

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(config, traffic):
    cfg = json.load(open(os.path.join(DATA, "configs", config + ".json")))
    tr = json.load(open(os.path.join(DATA, "traffic", traffic + ".json")))
    return cfg, tr


def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in tree.items()}


def test_gpt2_reference_matches_the_repos_model():
    from distributed_training_pytorch_tpu.models.transformer_lm import TransformerLM, make_fused_lm_loss

    cfg, tr = _load("lm-tiny", "tiny_t128_b8")
    params = gpt2.init_params(cfg, tr, jax.random.key(3))
    w = traffic_lib.make_data(cfg, tr, 5)["windows"][:4]
    batch = {"image": jnp.asarray(w[:, :-1]), "label": jnp.asarray(w[:, 1:])}
    model = TransformerLM(vocab_size=cfg["vocab_size"], hidden_dim=cfg["n_embd"], depth=cfg["n_layer"],
                          num_heads=cfg["n_head"], mlp_dim=cfg["n_inner"], max_len=128,
                          dtype=jnp.float32, attention_impl="plain")
    loss_fn = make_fused_lm_loss(model)

    def program(p):
        return loss_fn(gpt2.to_program(p, cfg), {}, batch, jax.random.key(0), True)[0]

    def reference(p):
        return gpt2.loss_sum(p, batch, cfg) / 4

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program)(params)
        lr, gr = jax.value_and_grad(reference)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    np_, nr = _norms(gp), _norms(gr)
    live = [k for k in nr if nr[k] > 1e-3 * np.median(list(nr.values()))]
    assert len(live) >= len(nr) - 2
    for k in live:
        assert np_[k] == pytest.approx(nr[k], rel=2e-3), k


def test_gpt2_leaves_split_both_layouts_alike():
    cfg, tr = _load("lm-tiny", "tiny_t128_b8")
    params = gpt2.init_params(cfg, tr, jax.random.key(1))
    params = {k: v + 0.01 * jax.random.normal(jax.random.key(7), v.shape) for k, v in params.items()}
    published = _norms(gpt2.leaves(params, cfg))
    program = _norms(gpt2.leaves(gpt2.from_program(gpt2.to_program(params, cfg), cfg), cfg))
    assert published.keys() == program.keys() and "h0.attn.c_attn.k.b" in published
    for k in published:
        assert program[k] == pytest.approx(published[k], rel=1e-6)


def test_vgg16_reference_matches_the_repos_model():
    from distributed_training_pytorch_tpu.models import InputNormalizer, create_model
    from distributed_training_pytorch_tpu.ops import cross_entropy_loss

    cfg, tr = _load("vgg-tiny", "tiny_img_b16")
    params = vgg16.init_params(cfg, tr, jax.random.key(3))
    data = traffic_lib.make_data(cfg, tr, 5)
    batch = {"image": jnp.asarray(data["images"][:8]), "label": jnp.asarray(data["labels"][:8])}
    model = InputNormalizer(
        create_model("vgg16", num_classes=10, dtype=jnp.float32, dropout_rate=0.0,
                     stage_features=tuple(cfg["stage_features"]), stage_layers=tuple(cfg["stage_layers"]),
                     classifier_widths=tuple(cfg["classifier_widths"])),
        mean=tuple(cfg["input"]["mean"]), std=tuple(cfg["input"]["std"]))

    def program(p):
        logits = model.apply({"params": vgg16.to_program(p, cfg)}, batch["image"], train=True)
        return cross_entropy_loss(logits, batch["label"])

    def reference(p):
        return vgg16.loss_sum(p, batch, cfg) / 8

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program)(params)
        lr, gr = jax.value_and_grad(reference)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    np_, nr = _norms(gp), _norms(gr)
    for k in nr:
        assert np_[k] == pytest.approx(nr[k], rel=2e-3, abs=1e-9), k


@pytest.mark.parametrize("kind", ["adamw", "sgd_momentum"])
def test_plain_optimizers_match_optax(kind):
    import optax

    opt = {"adamw": {"kind": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
           "sgd_momentum": {"kind": "sgd_momentum", "lr": 0.01, "momentum": 0.9, "weight_decay": 5e-4}}[kind]
    opt["schedule"] = {"warmup_epochs": 1, "total_epochs": 10}
    steps_per_epoch = 4
    sched = optax.warmup_cosine_decay_schedule(0.0, opt["lr"], 4, 40, 0.0)
    tx = (optax.adamw(sched, weight_decay=0.1, b1=0.9, b2=0.95) if kind == "adamw"
          else optax.chain(optax.add_decayed_weights(5e-4), optax.sgd(sched, momentum=0.9)))
    p = {"w": jnp.linspace(-1.0, 1.0, 12).reshape(3, 4), "b": jnp.ones((4,))}
    q, state, ref_state = p, tx.init(p), common.optimizer_init(p, opt)
    for step in range(7):
        g = jax.tree.map(lambda x: jnp.sin(x * (step + 1)), p)
        updates, state = tx.update(g, state, q)
        q = optax.apply_updates(q, updates)
        lr = common.schedule_lr(opt, step, steps_per_epoch)
        assert float(lr) == pytest.approx(float(sched(step)), rel=1e-6, abs=1e-12)
        p, ref_state = common.optimizer_update(p, g, ref_state, opt, step, lr)
    for k in p:
        np.testing.assert_allclose(np.asarray(p[k]), np.asarray(q[k]), rtol=2e-5, atol=1e-7)
