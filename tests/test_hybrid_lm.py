"""The hybrid state-space / attention LM (``models/hybrid_lm.py``,
``ops/ssd.py``) against its plain float32 reference
(``benchmarks/reference/granite_hybrid.py``: the sequential recurrence, a
masked softmax, the full logits), on seeded weights, on the CPU.

The preset: 2 mamba + 1 attention + 1 mamba layers, hidden 64, 4 heads of 16
over 2 key/value heads, 8 scan heads of 16 with 16 states, chunk 8,
vocabulary 97, Granite's four multipliers.

Tolerances. Program and reference compute the same mathematics in float32 in
different orders (chunked matmuls against a step-by-step recurrence, a fused
head against full logits), so they differ by accumulated round-off: over six
seeds and four lengths the worst leaf's gradient (always one of the scan's
own: `A_log`, `dt_bias`, `D`) read a relative gap of 5.7e-7 to 1.6e-6 and the
loss at most 2.1e-7, and FLOAT32_GAP is six times the largest. With bfloat16
matmul operands the worst leaf reads 1.4e-2 to 4.2e-2 on the same cases and
even the best leaf 2.1e-3, two hundred times the tolerance:
`test_bfloat16_compute_is_outside_the_float32_tolerance` holds the tolerance
to that. (The loss itself hardly moves, 1e-6: at these widths it sits at
ln 97 whatever the weights do, so the gradients carry the comparison.)
Rematerialised against not is the same arithmetic fused differently: at most
1.1e-6 on the worst leaf over the same seeds, REMAT_GAP five times that."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid as ref
from distributed_training_pytorch_tpu.models import HybridConfig, HybridLM, HybridTiny, JoyAITiny, NemotronHTiny, hybrid_lm
from distributed_training_pytorch_tpu.models.hybrid_lm import ATTENTION, MLA
from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss
from distributed_training_pytorch_tpu.ops import dispatch
from distributed_training_pytorch_tpu.ops.ssd import causal_conv1d, ssd_chunked

from test_engine import CHAINED_VS_SINGLE_ULPS, assert_trees_within_ulps

FLOAT32_GAP = 1e-5  # relative, of a loss or of a leaf's gradient by its norm: six times the largest round-off read
REMAT_GAP = 5e-6  # the same operations computed twice: round-off of a different fusion, no more

# one definition of the preset: the benchmark's CPU tests run the same file through the harness
with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "tests", "data_hybrid", "configs", "hybrid-tiny.json")) as f:
    CFG = json.load(f)


def make_batch(seed, rows, t):
    tokens = np.random.default_rng(seed).integers(0, CFG["vocab_size"], (rows, t + 1))
    return {"image": jnp.asarray(tokens[:, :-1], jnp.int32), "label": jnp.asarray(tokens[:, 1:], jnp.int32)}


def program_loss(params, batch, dtype=jnp.float32, remat=True):
    """The trainer's own loss function over the reference's names."""
    model = HybridLM(HybridConfig.from_dict(CFG), dtype=dtype, remat=remat)
    loss, _ = make_fused_lm_loss(model)(ref.to_program(params, CFG), {}, batch, jax.random.key(0), True)
    return loss


def reference_loss(params, batch):
    return ref.loss_sum(params, batch, CFG) / batch["label"].shape[0]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def worst_gap(got, want):
    """(largest relative gap over the leaves, its leaf)."""
    return max((rel(got[k], want[k]), k) for k in want)


@pytest.mark.parametrize("seed,t", [(0, 16), (1, 37), (2, 40)])
def test_loss_and_every_leafs_gradient_match_the_reference(seed, t):
    params = ref.init_params(CFG, {}, jax.random.key(seed))
    batch = make_batch(seed, 3, t)
    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(params, batch)
    assert abs(float(loss) - float(want_loss)) <= FLOAT32_GAP * abs(float(want_loss))
    assert set(grads) == set(want) == set(ref.param_shapes(CFG, {}))
    assert all(float(jnp.linalg.norm(g)) > 0 for g in want.values())  # every leaf is on the path
    gap, leaf = worst_gap(grads, want)
    assert gap <= FLOAT32_GAP, (gap, leaf)


def scan_inputs(seed, t, rows=2, heads=3, p=4, n=5):
    k = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(k[0], (rows, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, t, heads)) - 2.0)
    a = -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0)
    return x, dt, a, jax.random.normal(k[3], (rows, t, n)), jax.random.normal(k[4], (rows, t, n))


@pytest.mark.parametrize("chunk", [4, 8, 64])
@pytest.mark.parametrize("t", [8, 24, 37])
def test_the_chunked_scan_matches_the_sequential_recurrence(t, chunk):
    """Forward and the gradient of every input, at lengths that are and are
    not multiples of the chunk, and with a chunk longer than the sequence."""
    args = scan_inputs(t * 100 + chunk, t)
    weights = jax.random.normal(jax.random.key(7), (2, t, 3, 4))  # a fixed cotangent: every output counts

    def total(fn):
        return lambda *a: jnp.sum(weights * fn(*a))

    y, grads = jax.jit(lambda *a: (ssd_chunked(*a, chunk=chunk),
                                   jax.grad(total(lambda *b: ssd_chunked(*b, chunk=chunk)), argnums=range(5))(*a)))(*args)
    want, want_grads = jax.jit(lambda *a: (ref.ssd_sequential(*a),
                                           jax.grad(total(ref.ssd_sequential), argnums=range(5))(*a)))(*args)
    assert y.shape == want.shape and rel(y, want) <= FLOAT32_GAP
    for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, want_grads, strict=True):
        assert rel(g, w) <= FLOAT32_GAP, (name, rel(g, w))


def test_the_scan_forgets_nothing_across_chunks_and_sees_no_future():
    """A change at step s moves no output before s and every output after it
    that the decay lets it reach (the state is carried from chunk to chunk)."""
    x, dt, a, b, c = scan_inputs(3, 24)
    dt = dt * 0.05  # slow decay: step 5 still reaches step 23, two chunks on
    y0 = ssd_chunked(x, dt, a, b, c, chunk=8)
    y1 = ssd_chunked(x.at[:, 5].add(1.0), dt, a, b, c, chunk=8)
    moved = np.abs(np.asarray(y1 - y0)).max(axis=(0, 2, 3))
    assert (moved[:5] == 0).all() and (moved[5:] > 0).all()


def test_the_convolution_at_its_left_edge():
    """Zeros before the sequence: the first K - 1 outputs see only the taps
    that have a step to meet, and no output sees a later step."""
    k = jax.random.split(jax.random.key(0), 3)
    x, w, b = jax.random.normal(k[0], (2, 6, 5)), jax.random.normal(k[1], (4, 5)), jax.random.normal(k[2], (5,))
    y = np.asarray(causal_conv1d(x, w, b))
    x, w, b = (np.asarray(v, np.float64) for v in (x, w, b))
    np.testing.assert_allclose(y[:, 0], b + w[3] * x[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[:, 1], b + w[3] * x[:, 1] + w[2] * x[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[:, 5], b + sum(w[j] * x[:, 2 + j] for j in range(4)), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, np.asarray(ref.conv1d_causal(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
                                                               jnp.asarray(b, jnp.float32))), rtol=1e-6, atol=1e-6)
    later = np.asarray(causal_conv1d(jnp.asarray(x, jnp.float32).at[:, 4].add(1.0), jnp.asarray(w, jnp.float32),
                                     jnp.asarray(b, jnp.float32)))
    assert (later[:, :4] == y[:, :4]).all() and (later[:, 4:] != y[:, 4:]).all()


def test_rematerialised_blocks_give_the_unrematerialised_result():
    params = ref.init_params(CFG, {}, jax.random.key(5))
    batch = make_batch(5, 2, 24)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=True)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: program_loss(p, batch, remat=False)))(params)
    assert abs(float(loss) - float(want_loss)) <= REMAT_GAP * abs(float(want_loss))
    gap, leaf = worst_gap(grads, want)
    assert gap <= REMAT_GAP, (gap, leaf)


def test_the_backward_pass_recomputes_each_block_only_when_asked():
    """What `remat_time_share` reads on the chip: the recomputed forward
    carries jax's `rematted_computation` in its operations' names."""
    params = ref.init_params(CFG, {}, jax.random.key(0))
    batch = make_batch(0, 1, 16)

    def names(remat):
        text = jax.jit(jax.grad(lambda p: program_loss(p, batch, remat=remat))).lower(params).as_text(debug_info=True)
        return re.findall(r'loc\("([^"]*)"', text)

    recomputed = [n for n in names(True) if "rematted_computation" in n]
    assert any("ssd_scan" in n for n in recomputed)
    # attention on its plain path carries none of the names REMAT_POLICY keeps:
    # its scores and softmax are recomputed with the rest of the block
    assert any("gqa_attention" in n and n.endswith("/exp") for n in recomputed), sorted(n for n in recomputed if "gqa" in n)
    assert not any("rematted_computation" in n for n in names(False))


def attention_layers(cfg):
    """Attention layers on the fused loss's path: the stack's, and the
    prediction module's block where there is one (the last layer's kinds)."""
    return sum(kind in (ATTENTION, MLA) for kind in cfg.layer_types + cfg.layer_types[-1:] * cfg.mtp_layers)


@pytest.mark.parametrize("pallas", [True, False], ids=["flash", "plain"])
@pytest.mark.parametrize("factory", [HybridTiny, NemotronHTiny, JoyAITiny], ids=lambda f: f.__name__)
def test_rematerialisation_runs_each_attention_layers_forward_kernel_once(factory, pallas, monkeypatch):
    """``REMAT_POLICY`` keeps the flash kernel's ``o`` and ``lse`` across the
    recomputation: the gradient program calls the forward kernel once an
    attention layer, as often as the backward (without the names it called it
    twice: 8 against 4 in ``JoyAITiny`` with its module). The plain path
    carries no names, so its program is the full rematerialisation it was,
    to the bit. Either way the result is the unrematerialised one."""
    batch = make_batch(3, 2, 32)

    def grad_fn(remat):
        model = factory(vocab_size=97, pallas=pallas, remat=remat)
        return jax.jit(jax.value_and_grad(lambda p: make_fused_lm_loss(model)(p, {}, batch, jax.random.key(0), True)[0]))

    model = factory(vocab_size=97, pallas=pallas)
    params = model.init(jax.random.key(3), batch["image"])["params"]
    dispatch.reset()
    try:
        text = grad_fn(True).lower(params).as_text()
        recs = dispatch.records()
    finally:
        dispatch.reset()
    # the scan's and the convolution's kernels (their jitted calls are named _fwd_call
    # and _bwd_call too) refuse these toy widths: every such call below is flash's
    assert not {(r["op"], r["path"]) for r in recs} & {("ssd", "pallas"), ("conv", "pallas")}, recs
    layers = attention_layers(model.cfg) if pallas else 0
    assert len(re.findall(r"call @_fwd_call\w*\(", text)) == layers
    assert len(re.findall(r"call @_bwd_call\w*\(", text)) == layers
    (rec,) = [r for r in recs if r["op"] == "remat"]
    assert (rec["model"], rec["path"]) == (model.cfg.dispatch_name, "keep_attention_residuals" if pallas else "full")
    loss, grads = grad_fn(True)(params)
    want_loss, want = grad_fn(False)(params)
    assert abs(float(loss) - float(want_loss)) <= REMAT_GAP * abs(float(want_loss))
    # a leaf with no gradient (the routers' selection-only bias) must stay at none
    gaps = jax.tree.map(lambda g, w: rel(g, w) if np.linalg.norm(w) else float(np.linalg.norm(g)), grads, want)
    assert max(jax.tree.leaves(gaps)) <= REMAT_GAP, gaps
    if not pallas:  # the program a policy-free nn.remat gives
        monkeypatch.setattr(hybrid_lm, "REMAT_POLICY", None)
        assert grad_fn(True).lower(params).as_text() == text
        assert jax.tree.all(jax.tree.map(np.array_equal, grad_fn(True)(params)[1], grads))


def test_bfloat16_compute_is_outside_the_float32_tolerance():
    """The tolerance would catch the next precision down: bfloat16 matmul
    operands move every leaf's gradient, the least moved too, by a hundred
    times the tolerance and more."""
    params = ref.init_params(CFG, {}, jax.random.key(1))
    batch = make_batch(1, 3, 37)
    want = jax.jit(jax.grad(reference_loss))(params, batch)
    grads = jax.jit(jax.grad(lambda p, b: program_loss(p, b, dtype=jnp.bfloat16)))(params, batch)
    gap, leaf = min((rel(grads[k], want[k]), k) for k in want)
    assert gap > 100 * FLOAT32_GAP, (gap, leaf)


def test_scopes_dispatch_records_and_the_remat_counter():
    dispatch.reset()
    try:
        model = HybridTiny(vocab_size=97)
        tokens = jnp.zeros((1, 16), jnp.int32)
        variables = model.init(jax.random.key(0), tokens)
        text = jax.jit(model.apply).lower(variables, tokens).as_text(debug_info=True)
        for scope in ("mamba_mixer", "mamba_mixer/mamba_conv", "mamba_mixer/ssd_scan", "gqa_attention", "gated_mlp"):
            assert scope in text, scope
        recs = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
        assert recs == {("hybrid_lm", "attention", "plain"), ("hybrid_lm", "ssd", "chunked"), ("hybrid_lm", "conv", "jnp"),
                        ("hybrid_lm", "remat", "full")}  # plain attention: nothing is kept across the recomputation
    finally:
        dispatch.reset()


def test_a_config_this_stack_cannot_run_is_refused():
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        HybridConfig.from_dict({**CFG, "num_local_experts": 8})
    with pytest.raises(NotImplementedError, match="position_embedding_type"):
        HybridConfig.from_dict({**CFG, "position_embedding_type": "rope"})
    # since PR 36 the published keys' own values are taken: groups of B / C, and a d_inner that is
    # heads x head size whatever mamba_expand x hidden_size would give (nemotron_h's are 4096 and 5376)
    assert HybridConfig.from_dict({**CFG, "mamba_n_groups": 2}).mamba_n_groups == 2
    assert HybridConfig.from_dict({**CFG, "mamba_n_heads": 4}).mamba_d_inner == 4 * CFG["mamba_d_head"] != 2 * CFG["hidden_size"]


# -- through Trainer, from the entry -----------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory, devices):
    """``examples/train_lm.py:LMTrainer`` at ``LM_SIZE=hybrid_tiny``: two
    steps as one chained window, and as two single steps."""
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
            seq_len=32, base_lr=3e-4, size="hybrid_tiny", moe_every=0, precision="fp32", max_epoch=1, batch_size=8,
            chain_steps=chain_steps, log_every=0, have_validate=False, save_period=None, num_workers=0, progress=False,
            save_folder=str(tmp_path_factory.mktemp(f"hybrid_chain{chain_steps}")), seed=3,
            mesh=mesh_lib.create_mesh(devices=devices[:1]),
        )
        start = jax.device_get(trainer.state.params)
        trainer.train()
        return trainer, start

    return run(2), run(1)


def test_the_entry_trains_the_hybrid_through_trainer(trained):
    (trainer, start), _ = trained
    assert isinstance(trainer.model, HybridLM) and trainer.model.remat
    assert int(trainer.state.step) == 2 and dict(trainer.engine.trace_counts) == {"chained_2": 1}
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), trainer.state.params, start)
    assert all(v > 0 for v in jax.tree.leaves(moved)), moved  # AdamW reached every leaf, the scan's own included


def test_two_chained_steps_equal_two_single_steps(trained):
    (chained, _), (single, _) = trained
    assert dict(single.engine.trace_counts) == {"train_step": 1}
    assert_trees_within_ulps(jax.device_get(chained.state.params), jax.device_get(single.state.params), CHAINED_VS_SINGLE_ULPS)
    assert_trees_within_ulps(jax.device_get(chained.state.opt_state), jax.device_get(single.state.opt_state),
                             CHAINED_VS_SINGLE_ULPS)
