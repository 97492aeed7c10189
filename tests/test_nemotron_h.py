"""The ``nemotron_h`` stack of ``models/hybrid_lm.py`` (one mixer a layer:
Mamba-2 with grouped ``B`` / ``C`` and a grouped gated norm, grouped-query
attention with a stated head size, ``parallel/moe.py:HeldExpertsMlp``; an
untied head) against its plain float32 reference
(``benchmarks/reference/nemotron_h.py``: the sequential recurrence, a masked
softmax, a dense walk over the held experts, the full logits), on seeded
weights, on the CPU.

The preset (``benchmarks/tests/data_nemotron/configs/nemotron-tiny.json``):
layers ``MEM*E``, hidden 64, 4 query heads of 32 (not hidden / heads = 16) over
2 key/value heads, 8 scan heads of 16 with 16 states in 2 groups, chunk 8, 8
published experts of width 48 of which the first 4 are held (the first of two
shares), top-3, a shared expert of 96, vocabulary 97. ``b_corr`` is drawn
non-zero here (the configuration's zeros would not show a selection that
ignored it).

Tolerances. Program and reference compute the same mathematics in float32 in
different orders (chunked matmuls against a step-by-step recurrence, a sorted
buffer and a grouped product against a masked dense walk, a fused head
against full logits), so they differ by accumulated round-off: over the six
seeds below the worst leaf's gradient read a relative gap of 6.0e-7 to 1.15e-6
(one of the scan's own, `D`, `A_log`, `dt_bias`, or once the router) and the
loss at most 1.05e-7; FLOAT32_GAP (``tests/test_hybrid_lm.py``'s 1e-5) is nine
times the largest. The router's scores differ by round-off too, so a
token whose third and fourth scores tie to the seventh digit could pick
another expert on one side: with 8 experts and a few hundred tokens no seed
here does, and a swap would read as 1e-2, not 1e-6. With bfloat16 compute
the leaves read 6.8e-3 (the best matched) to 2.1e-2: even the best is six
hundred times the tolerance. Rematerialised against not read 0 on every leaf."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from benchmarks.systems.nemotron_h import hybrid_config
from distributed_training_pytorch_tpu.models import HybridLM, NemotronHTiny
from distributed_training_pytorch_tpu.models.hybrid_lm import ATTENTION, MAMBA, MOE, RMSNorm
from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss
from distributed_training_pytorch_tpu.ops import dispatch

from test_engine import CHAINED_VS_SINGLE_ULPS, assert_trees_within_ulps
from test_hybrid_lm import FLOAT32_GAP, REMAT_GAP, rel

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "..", "benchmarks", "tests", "data_nemotron", "configs", "nemotron-tiny.json")) as f:
    CFG = json.load(f)
with open(os.path.join(HERE, "..", "benchmarks", "configs", "nemotron-3-nano-30b-a3b.json")) as f:
    PUBLISHED = json.load(f)
BIAS = "e_score_correction_bias"


def make_params(seed):
    """The assumed initialisation, with the selection-only bias drawn non-zero."""
    params = ref.init_params(CFG, {}, jax.random.key(seed))
    for i, name in enumerate(sorted(n for n in params if n.endswith(BIAS))):
        params[name] = 0.1 * jax.random.normal(jax.random.fold_in(jax.random.key(seed + 100), i), params[name].shape)
    return params


def make_batch(seed, rows, t):
    tokens = np.random.default_rng(seed).integers(0, CFG["vocab_size"], (rows, t + 1))
    return {"image": jnp.asarray(tokens[:, :-1], jnp.int32), "label": jnp.asarray(tokens[:, 1:], jnp.int32)}


def program_loss(params, batch, dtype=jnp.float32, remat=True):
    """The trainer's own loss function over the reference's names."""
    model = HybridLM(hybrid_config(CFG), dtype=dtype, remat=remat)
    loss, _ = make_fused_lm_loss(model)(ref.to_program(params, CFG), {}, batch, jax.random.key(0), True)
    return loss


def reference_loss(params, batch):
    return ref.loss_sum(params, batch, CFG) / batch["label"].shape[0]


def worst_gap(got, want):
    """(largest relative gap over the leaves a gradient reaches, its leaf)."""
    return max((rel(got[k], want[k]), k) for k in want if not k.endswith(BIAS))


@pytest.mark.parametrize("seed,t", [(0, 16), (1, 37), (2, 40), (3, 24), (4, 33), (5, 64)])
def test_loss_and_every_leafs_gradient_match_the_reference(seed, t):
    params, batch = make_params(seed), make_batch(seed, 3, t)
    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    assert abs(float(loss) - float(want_loss)) <= FLOAT32_GAP * abs(float(want_loss))
    assert set(grads) == set(want) == set(ref.param_shapes(CFG, {}))
    for name, g in want.items():  # every leaf is on the path but the bias, which only selects
        assert (float(jnp.linalg.norm(g)) > 0) is (not name.endswith(BIAS)), name
        assert not name.endswith(BIAS) or float(jnp.linalg.norm(grads[name])) == 0
    gap, leaf = worst_gap(grads, want)
    assert gap <= FLOAT32_GAP, (gap, leaf)


def test_bfloat16_compute_is_outside_the_float32_tolerance():
    params, batch = make_params(1), make_batch(1, 3, 37)
    want = jax.jit(jax.grad(reference_loss))(params, batch)
    grads = jax.jit(jax.grad(lambda p, b: program_loss(p, b, dtype=jnp.bfloat16)))(params, batch)
    gap, leaf = min((rel(grads[k], want[k]), k) for k in want if not k.endswith(BIAS))
    assert gap > 100 * FLOAT32_GAP, (gap, leaf)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_grouped_gated_norm_is_a_reshape_and_a_normalisation(groups):
    x = jax.random.normal(jax.random.key(groups), (2, 5, 64)) * 3.0
    norm = RMSNorm(1e-5, jnp.float32, groups)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(9), (64,))
    got = norm.apply({"params": {"scale": scale}}, x)
    runs = x.reshape(2, 5, groups, 64 // groups)
    want = (runs / jnp.sqrt(jnp.mean(runs**2, -1, keepdims=True) + 1e-5)).reshape(x.shape) * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(ref._rms_norm(x, scale, 1e-5, groups)), np.asarray(want), rtol=2e-6, atol=2e-6)
    if groups > 1:  # and it is not the ungrouped norm
        assert rel(got, RMSNorm(1e-5, jnp.float32).apply({"params": {"scale": scale}}, x)) > 1e-2


def test_rematerialised_blocks_give_the_unrematerialised_result():
    params, batch = make_params(5), make_batch(5, 2, 24)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=True)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=False)))(params)
    assert abs(float(loss) - float(want_loss)) <= REMAT_GAP * abs(float(want_loss))
    gap, leaf = worst_gap(grads, want)
    assert gap <= REMAT_GAP, (gap, leaf)


def test_the_published_configuration_builds_the_published_widths():
    """``d_inner`` is heads x head size (4096), not ``expand`` x hidden (5376);
    the attention head is the stated 128, not hidden / heads (84); the router
    scores the published 128 and this chip holds experts 0-7."""
    cfg = hybrid_config(PUBLISHED)
    assert cfg.layer_types == (MAMBA, MOE, MAMBA, MOE, MAMBA, ATTENTION, MOE, MAMBA, MOE) and cfg.moe_layers == 4
    assert (cfg.mamba_d_inner, cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_chunk_size) == (4096, 8, 128, 128)
    assert PUBLISHED["expand"] * cfg.hidden_size == 5376 != cfg.mamba_d_inner
    assert (cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads) == (128, 32, 2) and cfg.hidden_size // 32 == 84
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (128, (0, 8), 6, 2.5)
    assert (cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size) == (1856, 3712)
    assert not cfg.tie_word_embeddings and not cfg.shared_intermediate_size and cfg.dispatch_name == "nemotron_h"
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling, cfg.attention_multiplier) == (1, 1, 1, None)
    shapes = jax.eval_shape(lambda: HybridLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 666_963_456 == PUBLISHED["parameters"]["count"]
    assert shapes["layer_0"]["mamba"]["conv_kernel"].shape == (4, 6144) and shapes["layer_5"]["self_attn"]["k_proj"]["kernel"].shape == (2688, 256)
    assert shapes["layer_1"]["moe"]["experts_up"].shape == (8, 2688, 1856) and shapes["layer_1"]["moe"]["router"].shape == (128, 2688)
    assert shapes["lm_head"].shape == shapes["embed"]["embedding"].shape == (16384, 2688)


@pytest.mark.parametrize("key,value", [("hybrid_override_pattern", "ME-M"), ("n_group", 2), ("mlp_hidden_act", "silu"),
                                       ("tie_word_embeddings", True), ("norm_topk_prob", False), ("n_shared_experts", 2)])
def test_a_nemotron_h_config_this_stack_cannot_run_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        hybrid_config({**CFG, key: value})


def test_scopes_dispatch_records_and_step_metrics():
    dispatch.reset()
    try:
        model = NemotronHTiny(vocab_size=97)
        tokens = jnp.zeros((1, 16), jnp.int32)
        variables = model.init(jax.random.key(0), tokens)
        text = jax.jit(lambda v, t: model.apply(v, t, mutable=["intermediates"])).lower(variables, tokens).as_text(debug_info=True)
        for scope in ("mamba_mixer/mamba_conv", "mamba_mixer/ssd_scan", "gqa_attention", "moe_layer/moe_router",
                      "moe_layer/moe_dispatch", "moe_layer/moe_experts", "moe_layer/moe_combine", "moe_layer/shared_expert"):
            assert scope in text, scope
        assert "gated_mlp" not in text  # a layer is its one mixer
        recs = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
        assert recs == {("nemotron_h", "attention", "plain"), ("nemotron_h", "ssd", "chunked"), ("nemotron_h", "moe_experts", "ragged_dot"),
                        ("nemotron_h", "moe_rows", "gather")}
        (reason,) = [r["reason"] for r in dispatch.records() if r["op"] == "moe_experts"]
        assert "ragged_dot over 4 held experts" in reason and "backend=cpu" in reason
        (reason,) = [r["reason"] for r in dispatch.records() if r["op"] == "moe_rows"]
        assert "backend=cpu" in reason and "TPU-default only" in reason  # off the chip the jax.numpy form: no test needs a TPU
        batch = make_batch(0, 2, 16)
        _, (metrics, _) = make_fused_lm_loss(model)(variables["params"], {}, batch, jax.random.key(0), True)
        offered = 2 * 16 * 3 * 2  # tokens x top-k x expert layers
        assert 0 < float(metrics["moe_pairs_local"]) < offered and float(metrics["moe_pairs_local"]).is_integer()
        assert 0 < float(metrics["moe_pairs_max_expert"]) <= 2 * 16
    finally:
        dispatch.reset()


def test_the_backward_pass_puts_its_gathers_under_the_layers_scopes():
    """What ``moe_time_share`` and ``moe_dispatch_time_share`` read on the chip:
    the written transposes of the two gathers carry the scopes themselves."""
    params, batch = make_params(0), make_batch(0, 1, 16)
    text = jax.jit(jax.grad(lambda p: program_loss(p, batch))).lower(params).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    for scope in ("moe_dispatch", "moe_combine", "moe_experts"):
        assert any("transpose" in n and "moe_layer" in n and scope in n for n in names), scope


# -- through Trainer, from the entry -----------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory, devices):
    """``examples/train_lm.py:LMTrainer`` at ``LM_SIZE=nemotron_h_tiny``: two
    steps as one chained window, and as two single steps."""
    from distributed_training_pytorch_tpu import profiling
    from distributed_training_pytorch_tpu.data import ArrayDataSource
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from examples.train_lm import LMTrainer

    class TwoSteps(LMTrainer):
        def build_train_dataset(self):
            w = self.windows[:16]
            return ArrayDataSource(image=w[:, :-1], label=w[:, 1:])

        build_val_dataset = build_train_dataset

    def run(chain_steps):
        trainer = TwoSteps(
            seq_len=32, base_lr=3e-4, size="nemotron_h_tiny", moe_every=0, precision="fp32", max_epoch=1, batch_size=8,
            chain_steps=chain_steps, log_every=0, have_validate=False, save_period=None, num_workers=0, progress=False,
            save_folder=str(tmp_path_factory.mktemp(f"nemotron_chain{chain_steps}")), seed=3,
            mesh=mesh_lib.create_mesh(devices=devices[:1]),
        )
        start = jax.device_get(trainer.state.params)
        trainer.train()
        return trainer, start

    profiling.install_recorder()
    try:
        chained = run(2)
        counted = profiling.counters().get("moe.pairs_local")
    finally:
        profiling.uninstall_recorder()
    return chained, run(1), counted


def test_the_entry_trains_the_stack_through_trainer(trained):
    (trainer, start), _, counted = trained
    assert isinstance(trainer.model, HybridLM) and trainer.model.remat and trainer.model.cfg.moe_layers == 2
    assert int(trainer.state.step) == 2 and dict(trainer.engine.trace_counts) == {"chained_2": 1}
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), trainer.state.params, start)
    bias = {k: v for k, v in jax.tree_util.tree_flatten_with_path(moved)[0] if "score_correction_bias" in jax.tree_util.keystr(k)}
    assert len(bias) == 2 and all(v == 0 for v in bias.values())  # zeros stay zeros: no gradient, and decay of nothing
    rest = [v for k, v in jax.tree_util.tree_flatten_with_path(moved)[0] if "score_correction_bias" not in jax.tree_util.keystr(k)]
    assert all(v > 0 for v in rest)  # AdamW reached every other leaf: each held expert, the router, the untied head
    # the routing both steps did reached the counter a reader sums: 2 steps x 8 rows x 32 tokens x top-3 x 2 layers offered
    assert counted is not None and 0 < counted < 2 * 8 * 32 * 3 * 2 and float(counted).is_integer()


def test_two_chained_steps_equal_two_single_steps(trained):
    (chained, _), (single, _), _ = trained
    assert dict(single.engine.trace_counts) == {"train_step": 1}
    assert_trees_within_ulps(jax.device_get(chained.state.params), jax.device_get(single.state.params), CHAINED_VS_SINGLE_ULPS)
    assert_trees_within_ulps(jax.device_get(chained.state.opt_state), jax.device_get(single.state.opt_state),
                             CHAINED_VS_SINGLE_ULPS)
