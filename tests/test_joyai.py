"""DeepSeek-V3's block as ``JoyAI-LLM-Flash`` publishes it, in
``models/hybrid_lm.py`` (latent attention on every layer, a dense SwiGLU MLP
in layer 0, routed SwiGLU experts after it, one multi-token-prediction
module, an untied head), and the pieces it brought: flash attention at a value
width of its own (``ops/pallas.py``), the interleaved rotary
(``ops/rotary.py``), the SwiGLU form of ``parallel/moe.py:HeldExpertsMlp``.
Each against a plain float32 form on the CPU: the kernels interpreted against
``_causal_plain``, the rotary against an explicit turn of each pair, the
stack against ``benchmarks/reference/joyai_llm_flash.py`` on seeded weights.

The preset (``benchmarks/tests/data_joyai/configs/joyai-tiny.json``): three
layers (dense, experts, experts), hidden 64, 4 heads with queries and keys
24 wide (16 + 8 rotary) and values 16, ranks 48 (queries) and 32 (the
latent), 8 published experts of width 32 of which the first 4 are held,
top-3, a shared expert of 32, vocabulary 97, one prediction module at weight
0.3. ``b_corr`` is drawn non-zero here.

Tolerances. Program and reference compute the same mathematics in float32 in
different orders (a sorted buffer and a grouped product against a masked
dense walk, a fused head a slice at a time against full logits, the rotary
as pairs against strided halves), so they differ by round-off: over the
seeds below the worst leaf's gradient read a relative gap of at most 5.1e-7
and the loss 0; FLOAT32_GAP (``tests/test_hybrid_lm.py``'s 1e-5) is twenty
times that. With bfloat16 compute the best-matched leaf reads over a hundred
times the tolerance. The flash kernels interpreted in float32 against the
plain masked softmax read at most 1.2e-6 (values and gradients);
FLASH_GAP is 1e-5."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_llm_flash as ref
from benchmarks.systems.joyai_llm_flash import hybrid_config
from distributed_training_pytorch_tpu.models import HybridLM, JoyAITiny
from distributed_training_pytorch_tpu.models.hybrid_lm import DENSE, MLA, MOE
from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss
from distributed_training_pytorch_tpu.ops import dispatch, rotary
from distributed_training_pytorch_tpu.ops.pallas import _bwd_vmem_bytes, _causal_plain, flash_attention

from test_hybrid_lm import FLOAT32_GAP, REMAT_GAP, rel

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "..", "benchmarks", "tests", "data_joyai", "configs", "joyai-tiny.json")) as f:
    CFG = json.load(f)
with open(os.path.join(HERE, "..", "benchmarks", "configs", "joyai-llm-flash.json")) as f:
    PUBLISHED = json.load(f)
BIAS = "e_score_correction_bias"
FLASH_GAP = 1e-5


# -- flash attention at a value width of its own ---------------------------------


def qkv(seed, t, dqk, dv, heads=2):
    k = jax.random.split(jax.random.key(seed), 4)
    q, key = (jax.random.normal(k[i], (1, t, heads, dqk)) for i in (0, 1))
    return q, key, jax.random.normal(k[2], (1, t, heads, dv)), jax.random.normal(k[3], (1, t, heads, dv))


# multi_block: three 128-row blocks a side, the loops over the mask; sub_tile: one 256 x 256 block pair walked as sub-tiles
PATHS = {"multi_block": (384, 128), "sub_tile": (256, None)}


@pytest.mark.parametrize("path", list(PATHS))
def test_flash_with_a_narrower_value_matches_plain_attention(path):
    t, block = PATHS[path]
    q, k, v, _ = qkv(0, t, 48, 32)
    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block, interpret=True)
    assert got.shape == v.shape
    assert rel(got, _causal_plain(q, k, v)) <= FLASH_GAP


@pytest.mark.parametrize("path", list(PATHS))
def test_flash_gradients_with_a_narrower_value_match_plain_attention(path):
    t, block = PATHS[path]
    q, k, v, w = qkv(1, t, 48, 32)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=block, block_k=block, interpret=True)  # noqa: E731
    got = jax.grad(lambda *a: jnp.sum(w * flash(*a)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * _causal_plain(*a)), argnums=(0, 1, 2))(q, k, v)
    for name, g, h, width in zip("qkv", got, want, (48, 48, 32)):
        assert g.shape[-1] == width and rel(g, h) <= FLASH_GAP, name


def test_flash_refuses_operands_that_do_not_pair():
    q, k, v, _ = qkv(2, 128, 24, 16)
    with pytest.raises(ValueError, match="Dv may differ"):
        flash_attention(q, k[..., :16], v)
    with pytest.raises(ValueError, match="Dv may differ"):
        flash_attention(q, k, v[:, :64])


@pytest.mark.parametrize("t,bq,bk,d", [(4096, 1024, 1024, 64), (8192, 1024, 1024, 64), (4096, 1024, 1024, 128)])
def test_the_backwards_limit_at_equal_widths_is_the_single_width_reckoning(t, bq, bk, d):
    """The one-width formula the limit had before v took its own width: the
    equal-width kernels are compiled under the same limit as ever."""
    lanes = -(-d // 128) * 128
    single = 3 * 2 * t * lanes * 2 + t * lanes * 4 + 2 * 2 * 8 * t * 4 + 4 * 2 * bk * lanes * 2 + 6 * bq * bk * 4
    assert _bwd_vmem_bytes(t, bq, bk, d, d, 2) == max(16 * 2**20, single)


def test_the_backwards_limit_counts_each_width_in_its_own_lanes():
    # 192 pads to 256 lanes, 128 stays 128: q / dq slabs and k / dk blocks at 256, dO and v / dv at 128
    got = _bwd_vmem_bytes(8192, 1024, 1024, 192, 128, 2)
    want = 2 * 8192 * (2 * 256 + 128) * 2 + 8192 * 256 * 4 + 2 * 2 * 8 * 8192 * 4 + 2 * 2 * 1024 * (256 + 128) * 2 + 6 * 1024 * 1024 * 4
    assert got == want
    assert _bwd_vmem_bytes(8192, 1024, 1024, 192, 192, 2) > got  # a v padded to 192 would hold more


# -- the rotary ----------------------------------------------------------------


def turned_by_hand(x, theta):
    """Each pair (x_2i, x_2i+1) at position p times the 2 x 2 rotation by p · theta^(-2i/D), in float64."""
    x = np.asarray(x, np.float64)
    t, width = x.shape[1], x.shape[-1]
    out = np.empty_like(x)
    for p in range(t):
        for i in range(width // 2):
            a = p * theta ** (-2.0 * i / width)
            turn = np.array([[np.cos(a), -np.sin(a)], [np.sin(a), np.cos(a)]])
            out[:, p, ..., 2 * i:2 * i + 2] = np.einsum("ij,...j->...i", turn, x[:, p, ..., 2 * i:2 * i + 2])
    return out


@pytest.mark.parametrize("shape,theta", [((2, 37, 8), 10000.0), ((2, 37, 3, 8), 10000.0), ((1, 64, 2, 64), 32e6)])
def test_the_rotary_turns_each_pair_in_place(shape, theta):
    x = jax.random.normal(jax.random.key(len(shape)), shape)
    got = rotary.rotate(x, jnp.arange(shape[1]), theta)
    np.testing.assert_allclose(np.asarray(got), turned_by_hand(x, theta), rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref.rope_pairs(x, theta)), turned_by_hand(x, theta), rtol=0, atol=2e-5)


def test_turned_queries_and_keys_meet_by_their_distance_alone():
    q, k = jax.random.normal(jax.random.key(3), (2, 8))
    at = lambda x, p: rotary.rotate(jnp.broadcast_to(x, (1, 40, 8)), jnp.arange(40), 10000.0)[0, p]  # noqa: E731
    dots = [float(at(q, p + 5) @ at(k, p)) for p in (0, 7, 30)]
    np.testing.assert_allclose(dots, dots[0], rtol=1e-5)
    assert abs(float(at(q, 12) @ at(k, 0)) - dots[0]) > 1e-3


# -- the SwiGLU experts ----------------------------------------------------------

SHARE_GAP = 5e-6  # tests/test_moe.py's HELD_GAP: the same sums in another order


def expert_cfg(first, count):
    return {"n_routed_experts": count, "experts_held_first": first, "published": {"n_routed_experts": 8},
            "num_experts_per_tok": 3, "routed_scaling_factor": 2.5, "hidden_size": 16, "num_attention_heads": 1,
            "qk_nope_head_dim": 1, "qk_rope_head_dim": 1, "v_head_dim": 1, "q_lora_rank": 1, "kv_lora_rank": 1,
            "moe_intermediate_size": 24, "n_shared_experts": 1}


def whole_layer(seed):
    k = jax.random.split(jax.random.key(seed), 6)
    return {"mlp.gate.w": jax.random.normal(k[0], (8, 16)), "mlp.gate.e_score_correction_bias": 0.3 * jax.random.normal(k[1], (8,)),
            "mlp.experts.gate_up_proj.w": 0.3 * jax.random.normal(k[2], (8, 16, 48)),
            "mlp.experts.down_proj.w": 0.3 * jax.random.normal(k[3], (8, 24, 16)),
            "mlp.shared_experts.gate_up_proj.w": 0.3 * jax.random.normal(k[4], (16, 48)),
            "mlp.shared_experts.down_proj.w": 0.3 * jax.random.normal(k[5], (24, 16))}


def share(p, first, count):
    """(the reference's parameters, the program's variables) for experts ``first … first + count − 1``."""
    experts = ("mlp.experts.gate_up_proj.w", "mlp.experts.down_proj.w")
    held = dict(p, **{k: p[k][first:first + count] for k in experts})
    tree = ref.to_program({"layers.1." + k: v for k, v in held.items()}, expert_cfg(first, count))["layer_1"]["mlp"]
    return held, {"params": tree}


def swiglu_layer(first, count):
    from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

    return HeldExpertsMlp(24, 24, 8, first, count, 3, 2.5, activation="swiglu")


def reference_layer(x, p, first, count):
    with jax.default_matmul_precision("highest"):
        return ref._moe(x, p, expert_cfg(first, count), lambda v: v)


@pytest.mark.parametrize("shares", [[(0, 4), (4, 4)], [(0, 2), (2, 2), (4, 2), (6, 2)], [(0, 3), (3, 5)]])
def test_the_swiglu_shares_add_up_to_the_uncut_layer(shares):
    """Each share gives its own experts' part of every token's sum plus the
    shared expert, which every chip computes alike: the shares' sum, the
    shared expert counted once, is the whole layer with all 8 experts held."""
    p = whole_layer(3)
    x = jax.random.normal(jax.random.key(5), (2, 19, 16))
    whole = reference_layer(x, p, 0, 8)
    shared_only = reference_layer(x, share(p, 0, 0)[0], 0, 0)
    parts = [swiglu_layer(first, count).apply(share(p, first, count)[1], x) for first, count in shares]
    total = sum(parts) - (len(shares) - 1) * shared_only
    assert rel(total, whole) <= SHARE_GAP
    assert all(rel(part, whole) > 1e-2 for part in parts)  # and no one share is the whole


@pytest.mark.parametrize("first,count", [(0, 4), (2, 5)])
def test_the_swiglu_layer_and_its_gradients_match_the_reference(first, count):
    ref_p, variables = share(whole_layer(first + count), first, count)
    x = jax.random.normal(jax.random.key(7), (2, 19, 16))
    w = jax.random.normal(jax.random.key(8), x.shape)
    got, grads = jax.value_and_grad(lambda v, x: jnp.sum(w * swiglu_layer(first, count).apply(v, x)), argnums=(0, 1))(variables, x)
    want, want_grads = jax.value_and_grad(lambda p, x: jnp.sum(w * reference_layer(x, p, first, count)), argnums=(0, 1))(ref_p, x)
    assert abs(float(got) - float(want)) <= SHARE_GAP * abs(float(want))
    assert rel(grads[1], want_grads[1]) <= SHARE_GAP
    back = share(want_grads[0], 0, count)[1]["params"]
    for name in ("experts_gate_up", "experts_down", "router"):
        assert rel(grads[0]["params"][name], back[name]) <= SHARE_GAP, name
    assert float(jnp.abs(grads[0]["params"]["score_correction_bias"]).max()) == 0


@pytest.mark.parametrize("tokens,k,taken", [(16384, 6, True), (16384, 8, True), (8192, 8, True), (32768, 8, False), (65536, 8, False)])
def test_the_row_kernels_take_a_layer_whose_pair_tables_fit_the_scalar_memory(tokens, k, taken):
    """``nemotron3nano_t8192``'s 98,304 pairs and ``joyaiflash_t8192``'s
    131,072 (top-8 of 16,384 tokens) are taken: the kernels hold one table of
    four bytes a pair whole and a tile's blocks, about 250,000 pairs in the 1 MiB
    the scalar core has. Past that (262,144 pairs: 1 MiB of ``pair`` alone) the
    layer keeps its ``jax.numpy`` form with the reason recorded."""
    from distributed_training_pytorch_tpu.ops.moe_rows import rows_refused

    why = rows_refused(tokens, k, 2048, jnp.bfloat16)
    assert (why is None) is taken, why
    assert taken or "scalar core's memory" in why


# -- the stack against its reference ---------------------------------------------


def make_params(seed):
    params = ref.init_params(CFG, {}, jax.random.key(seed))
    for i, name in enumerate(sorted(n for n in params if n.endswith(BIAS))):
        params[name] = 0.1 * jax.random.normal(jax.random.fold_in(jax.random.key(seed + 100), i), params[name].shape)
    return params


def make_batch(seed, rows, t):
    tokens = np.random.default_rng(seed).integers(0, CFG["vocab_size"], (rows, t + 1))
    return {"image": jnp.asarray(tokens[:, :-1], jnp.int32), "label": jnp.asarray(tokens[:, 1:], jnp.int32)}


def program_loss(params, batch, dtype=jnp.float32, remat=True, aux=False):
    model = HybridLM(hybrid_config(CFG), dtype=dtype, remat=remat)
    loss, (metrics, _) = make_fused_lm_loss(model)(ref.to_program(params, CFG), {}, batch, jax.random.key(0), True)
    return (loss, metrics) if aux else loss


def reference_loss(params, batch):
    return ref.loss_sum(params, batch, CFG) / batch["label"].shape[0]


def worst_gap(got, want):
    return max((rel(got[k], want[k]), k) for k in want if not k.endswith(BIAS))


@pytest.mark.parametrize("seed,t", [(0, 16), (1, 37), (2, 40), (3, 64)])
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


def test_the_prediction_modules_loss_is_its_own_term():
    """``mtp_loss`` in the step's metrics is the module's mean NLL of the token
    after the next; the objective is the first head's plus 0.3 times it."""
    params, batch = make_params(4), make_batch(4, 2, 24)
    loss, metrics = program_loss(params, batch, aux=True)
    with jax.default_matmul_precision("highest"):
        h, mtp = ref.hidden_states(params, batch["image"], CFG, labels=batch["label"])
        main = float(jnp.mean(ref._nll(h, params["lm_head.w"], batch["label"], lambda v: v)))
        ahead = float(jnp.mean(ref._nll(mtp[:, :-1], params["lm_head.w"], batch["label"][:, 1:], lambda v: v)))
    assert float(metrics["nll"]) == pytest.approx(main, rel=FLOAT32_GAP)
    assert float(metrics["mtp_loss"]) == pytest.approx(ahead, rel=FLOAT32_GAP)
    assert float(loss) == pytest.approx(main + 0.3 * ahead, rel=FLOAT32_GAP)
    # the last position predicts nothing: its target may be anything
    moved = dict(batch, label=batch["label"].at[:, -1].set((batch["label"][:, -1] + 1) % CFG["vocab_size"]))
    assert float(program_loss(params, moved, aux=True)[1]["mtp_loss"]) != float(metrics["mtp_loss"])  # it feeds the module at T-1


def test_bfloat16_compute_is_outside_the_float32_tolerance():
    params, batch = make_params(1), make_batch(1, 3, 37)
    want = jax.jit(jax.grad(reference_loss))(params, batch)
    grads = jax.jit(jax.grad(lambda p, b: program_loss(p, b, dtype=jnp.bfloat16)))(params, batch)
    gap, leaf = min((rel(grads[k], want[k]), k) for k in want if not k.endswith(BIAS))
    assert gap > 100 * FLOAT32_GAP, (gap, leaf)


def test_rematerialised_blocks_give_the_unrematerialised_result():
    params, batch = make_params(5), make_batch(5, 2, 24)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=True)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=False)))(params)
    assert abs(float(loss) - float(want_loss)) <= REMAT_GAP * abs(float(want_loss))
    gap, leaf = worst_gap(grads, want)
    assert gap <= REMAT_GAP, (gap, leaf)


def test_without_labels_the_model_gives_the_first_heads_logits():
    model = JoyAITiny(vocab_size=97)
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = model.init(jax.random.key(0), tokens)
    assert set(variables["params"]["mtp"]) == {"hnorm", "enorm", "eh_proj", "block", "final_norm"}
    assert model.apply(variables, tokens).shape == (1, 16, 97)
    hidden = model.apply(variables, tokens, return_hidden=True, labels=tokens)
    assert isinstance(hidden, tuple) and [h.shape for h in hidden] == [(1, 16, 64)] * 2


# -- the configuration -------------------------------------------------------------


def test_the_published_configuration_builds_the_published_widths():
    cfg = hybrid_config(PUBLISHED)
    assert cfg.layer_types == (MLA,) * 5 and cfg.ffn_types == (DENSE, MOE, MOE, MOE, MOE) and cfg.moe_layers == 5
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (256, (0, 16), 8, 2.5)
    assert (cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size, cfg.shared_intermediate_size) == (768, 768, 7168)
    assert (cfg.rope_theta, cfg.mtp_layers, cfg.mtp_loss_weight, cfg.moe_activation) == (32e6, 1, 0.3, "swiglu")
    shapes = jax.eval_shape(lambda: HybridLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 680_441_088 == PUBLISHED["parameters"]["count"]
    attn = shapes["layer_1"]["self_attn"]
    assert attn["q_b_proj"]["kernel"].shape == (1536, 32 * 192) and attn["kv_b_proj"]["kernel"].shape == (512, 32 * 256)
    assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (2048, 576) and attn["o_proj"]["kernel"].shape == (32 * 128, 2048)
    assert shapes["layer_0"]["mlp_in"]["kernel"].shape == (2048, 2 * 7168) and "mlp" not in shapes["layer_0"]
    assert shapes["layer_1"]["mlp"]["experts_gate_up"].shape == (16, 2048, 1536) and shapes["layer_1"]["mlp"]["router"].shape == (256, 2048)
    assert shapes["mtp"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["lm_head"].shape == shapes["embed"]["embedding"].shape == (16160, 2048)


@pytest.mark.parametrize("key,value", [("rope_scaling", {"type": "yarn"}), ("n_group", 8), ("topk_group", 4),
                                       ("scoring_func", "softmax"), ("num_nextn_predict_layers", 2), ("attention_bias", True)])
def test_a_config_this_stack_cannot_run_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        hybrid_config({**CFG, key: value})


def test_scopes_and_dispatch_records():
    dispatch.reset()
    try:
        model = JoyAITiny(vocab_size=97)
        batch = make_batch(0, 1, 16)
        params = model.init(jax.random.key(0), batch["image"])["params"]
        loss_fn = make_fused_lm_loss(model)
        text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, batch, jax.random.key(0), True)[0])).lower(params).as_text(debug_info=True)
        for scope in ("mla_attention/mla_q", "mla_attention/mla_kv", "mla_attention/mla_rope", "gated_mlp", "moe_layer/moe_experts",
                      "mtp/block", "mtp_loss_head"):
            assert scope in text, scope
        recs = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
        assert recs == {("joyai_llm_flash", "attention", "plain"), ("joyai_llm_flash", "moe_experts", "ragged_dot"),
                        ("joyai_llm_flash", "moe_rows", "gather"), ("joyai_llm_flash", "remat", "full")}
        (experts,) = [r for r in dispatch.records() if r["op"] == "moe_experts"]
        assert experts["activation"] == "swiglu"
    finally:
        dispatch.reset()


def test_forced_flash_records_both_widths():
    dispatch.reset()
    try:
        model = JoyAITiny(vocab_size=97, pallas=True)
        tokens = jnp.zeros((1, 128), jnp.int32)
        jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))
        (rec,) = [r for r in dispatch.records() if r["op"] == "attention"]
        assert rec["path"] == "flash" and (rec["head_dim_qk"], rec["head_dim_v"]) == (24, 16)
    finally:
        dispatch.reset()


# -- through Trainer, from the entry -------------------------------------------------


def test_the_entry_trains_the_stack_through_trainer(tmp_path, devices):
    """``examples/train_lm.py:LMTrainer`` at ``LM_SIZE=joyai_tiny``: two steps as
    one chained window; every leaf but the selection bias moves, and the
    module's loss is in the step's metrics."""
    from distributed_training_pytorch_tpu.data import ArrayDataSource
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
    from examples.train_lm import LMTrainer

    class TwoSteps(LMTrainer):
        def build_train_dataset(self):
            w = self.windows[:16]
            return ArrayDataSource(image=w[:, :-1], label=w[:, 1:])

        build_val_dataset = build_train_dataset

        def _aggregate_epoch_metrics(self, host, synced=0):
            self.seen = host
            return super()._aggregate_epoch_metrics(host, synced)

    trainer = TwoSteps(
        seq_len=32, base_lr=3e-4, size="joyai_tiny", moe_every=0, precision="fp32", max_epoch=1, batch_size=8,
        chain_steps=2, log_every=0, have_validate=False, save_period=None, num_workers=0, progress=False,
        save_folder=str(tmp_path), seed=3, mesh=mesh_lib.create_mesh(devices=devices[:1]),
    )
    start = jax.device_get(trainer.state.params)
    trainer.train()
    assert isinstance(trainer.model, HybridLM) and trainer.model.remat and trainer.model.cfg.mtp_layers == 1
    assert int(trainer.state.step) == 2 and dict(trainer.engine.trace_counts) == {"chained_2": 1}
    moved = jax.tree_util.tree_flatten_with_path(jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                                                              trainer.state.params, start))[0]
    assert all((v == 0) is ("score_correction_bias" in jax.tree_util.keystr(k)) for k, v in moved)
    assert len(trainer.seen) == 2 and all(0 < float(m["mtp_loss"]) < 10 for m in trainer.seen)
