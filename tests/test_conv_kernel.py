"""The Mamba-2 mixer's causal convolution as Pallas kernels
(``ops/causal_conv.py:causal_conv_silu``, ``conv_fwd`` / ``conv_bwd`` with their
written backward), interpreted on the CPU, against the ``jax.numpy`` form they
replace on the chip, ``silu(ops/ssd.py:causal_conv1d(...)).astype(dtype)``.

Shapes: the mixer's at CPU size. The kernels read the convolution's channels
out of the whole input projection (``[z | xBC | dt]``), so ``x`` is
``[B, T, d_inner + C + heads]`` and the convolution starts at ``d_inner``: one
group of ``B`` / ``C`` (``C = d_inner + 2·16``, granite-4.0-h's form) and eight
(``d_inner + 16·16``, nemotron-3-nano's). ``T`` of three and of five lane
chunks, so the steps carried from one chunk to the next are crossed; both
ragged against the 512 a chunk the cells' ``T`` take.

Tolerances. The forward sums the taps in the ``jax.numpy`` form's order and
rounds where it rounds: ``y`` is its value to the bit on the CPU, and the test
allows one bfloat16 ulp (the chip's ``exp`` may differ from the CPU's). The
backward takes ``dpre = dy · σ(1 + pre(1 − σ))`` where autodiff sums the two
terms of the product rule, and sums ``dx``'s taps and ``dw``'s steps in its
own order: float32 gradients within ``FLOAT32_GAP`` (1e-5; read 8e-8 to
2.4e-7), bfloat16 ``dx`` within one bfloat16 rounding by norm (read 6e-6 to
1.8e-4 of the largest element: a float32 difference in the last place flips a
rounding now and then)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.ops import dispatch
from distributed_training_pytorch_tpu.ops.causal_conv import causal_conv_silu, conv_tiles
from distributed_training_pytorch_tpu.ops.ssd import causal_conv1d

from test_hybrid_lm import FLOAT32_GAP, rel

D_INNER, HEADS, STATES, TAPS = 128, 16, 16, 4
BFLOAT16_DX_GAP = 2**-8  # relative, by norm: one rounding of a bfloat16 gradient

# name: (rows, T, groups)
CASES = {
    "one_group_three_chunks": (2, 384, 1),
    "eight_groups_five_chunks": (1, 640, 8),
}


def conv_inputs(seed, rows, t, groups, dtype):
    """The whole projection ``[B, T, d_inner + C + heads]``, the taps, the bias and a fixed cotangent."""
    c = D_INNER + 2 * groups * STATES
    k = jax.random.split(jax.random.key(seed), 4)
    proj = jax.random.normal(k[0], (rows, t, D_INNER + c + HEADS)).astype(dtype)
    w = jax.random.uniform(k[1], (TAPS, c), minval=-0.5, maxval=0.5)
    b = 0.1 * jax.random.normal(k[2], (c,))
    return (proj, w, b), jax.random.normal(k[3], (rows, t, c))


def jnp_form(proj, w, b, dtype):
    return jax.nn.silu(causal_conv1d(proj[..., D_INNER:D_INNER + w.shape[1]], w, b)).astype(dtype)


def kernels(proj, w, b, dtype):
    return causal_conv_silu(proj, w, b, start=D_INNER, dtype=dtype, interpret=True)


def with_gradients(fn, weights, dtype):
    def both(*a):
        y, vjp = jax.vjp(lambda *v: fn(*v, dtype), *a)
        return (y,) + vjp(weights.astype(dtype))
    return jax.jit(both)


@pytest.fixture(scope="module")
def results():
    """Each case in each dtype once: the kernels' and the ``jax.numpy`` form's ``(y, dx, dw, db)``."""
    out = {}
    for seed, (name, (rows, t, groups)) in enumerate(CASES.items()):
        for dtype in (jnp.float32, jnp.bfloat16):
            args, weights = conv_inputs(seed, rows, t, groups, dtype)
            out[name, jnp.dtype(dtype).name] = (with_gradients(kernels, weights, dtype)(*args),
                                                with_gradients(jnp_form, weights, dtype)(*args))
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_forward_is_the_jnp_forms_to_one_ulp(results, case, dtype):
    (got, *_), (want, *_) = results[case, dtype]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    ulp = np.abs(want) * 2.0 ** (-23 if dtype == "float32" else -7) + np.finfo(np.float32).tiny
    assert (np.abs(got - want) <= ulp).all(), np.abs(got - want).max()


@pytest.mark.parametrize("name", ["dx", "dw", "db"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_written_backward_matches_autodiff_of_the_jnp_form(results, case, dtype, name):
    got, want = results[case, dtype]
    i = ("y", "dx", "dw", "db").index(name)
    g, w = got[i], want[i]
    assert g.shape == w.shape and g.dtype == w.dtype
    # dx has the projection's shape: zeros outside the convolution's channels, which the kernels never touch
    if name == "dx":
        assert not np.asarray(g[..., :D_INNER]).any() and not np.asarray(g[..., D_INNER + got[2].shape[1]:]).any()
    gap = BFLOAT16_DX_GAP if (name, dtype) == ("dx", "bfloat16") else FLOAT32_GAP
    assert rel(g, w) <= gap, rel(g, w)


def test_no_step_sees_a_later_one():
    """A change at step ``s`` moves no output before ``s``, and the gradient
    of the outputs before ``s`` reaches no step from ``s`` on; ``s`` is the
    first step of a lane chunk, where the carried steps meet the chunk's own."""
    (proj, w, b), _ = conv_inputs(3, 1, 384, 1, jnp.float32)
    s = 128
    y0 = kernels(proj, w, b, jnp.float32)
    y1 = kernels(proj.at[:, s].add(1.0), w, b, jnp.float32)
    moved = np.abs(np.asarray(y1 - y0)).max(axis=(0, 2))
    assert (moved[:s] == 0).all() and (moved[s:s + TAPS] > 0).all() and (moved[s + TAPS:] == 0).all()
    dx = jax.grad(lambda p: jnp.sum(kernels(p, w, b, jnp.float32)[:, :s]))(proj)
    assert not np.asarray(dx[:, s:]).any() and np.asarray(dx[:, s - TAPS + 1:s, D_INNER:D_INNER + w.shape[1]]).all()


@pytest.mark.parametrize("knob,path,reason", [
    (None, "jnp", "auto: backend=cpu (the conv kernels are TPU-default only)"),
    (False, "jnp", "pallas=False"),
    (True, "pallas", "pallas=True (forced), backend=cpu: 160 channels, T 384, 4 taps"),
])
def test_the_knob_picks_the_path_and_the_record_says_why(knob, path, reason):
    (proj, w, b), _ = conv_inputs(0, 1, 384, 1, jnp.float32)
    dispatch.reset()
    try:
        y = dispatch.conv_fn("hybrid_lm", knob)(proj, w, b, start=D_INNER, dtype=jnp.float32)
        assert rel(y, jnp_form(proj, w, b, jnp.float32)) <= FLOAT32_GAP
        (rec,) = dispatch.records()
        assert (rec["model"], rec["op"], rec["path"], rec["reason"]) == ("hybrid_lm", "conv", path, reason)
    finally:
        dispatch.reset()


@pytest.mark.parametrize("c,t,start,why", [
    (160, 200, 128, "T 200 is no multiple of 128"),
    (168, 384, 128, "168 channels from channel 128 are no whole groups of 16"),
    (160, 384, 120, "160 channels from channel 120 are no whole groups of 16"),
])
def test_a_shape_the_kernels_refuse_falls_to_the_jnp_form_with_the_reason(c, t, start, why):
    assert conv_tiles(c, t, TAPS, start) == why
    with pytest.raises(ValueError, match=why):
        causal_conv_silu(jnp.zeros((1, t, start + c)), jnp.zeros((TAPS, c)), jnp.zeros((c,)), start=start, interpret=True)
    k = jax.random.split(jax.random.key(1), 3)
    proj = jax.random.normal(k[0], (1, t, start + c))
    w, b = jax.random.normal(k[1], (TAPS, c)), jax.random.normal(k[2], (c,))
    dispatch.reset()
    try:
        y = dispatch.conv_fn("nemotron_h", True)(proj, w, b, start=start, dtype=jnp.float32)
        (rec,) = dispatch.records()
        assert (rec["model"], rec["op"], rec["path"]) == ("nemotron_h", "conv", "jnp") and why in rec["reason"]
    finally:
        dispatch.reset()
    want = jax.nn.silu(causal_conv1d(proj[..., start:], w, b))
    assert np.array_equal(np.asarray(y), np.asarray(want))


def test_the_model_hands_its_knob_to_the_conv():
    """``NemotronH``-shaped stack with ``pallas=True`` at a ``T`` the conv's
    kernels take (the scan's refuse its toy widths and stay chunked): the loss
    and every leaf's gradient are the ``pallas=False`` model's, through
    ``nn.remat``, and the record names the conv's kernels."""
    from distributed_training_pytorch_tpu.models import HybridConfig, HybridLM

    cfg = HybridConfig(vocab_size=61, hidden_size=64, layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
                       num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
                       mamba_chunk_size=64, dispatch_name="nemotron_h")
    tokens = jax.random.randint(jax.random.key(1), (2, 256), 0, cfg.vocab_size)
    variables = HybridLM(cfg).init(jax.random.key(0), tokens)

    def loss(variables, pallas):
        logits = HybridLM(cfg, pallas=pallas).apply(variables, tokens)
        return -jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    dispatch.reset()
    try:
        got, grads = jax.jit(jax.value_and_grad(lambda v: loss(v, True)))(variables)
        paths = {(r["model"], r["op"], r["path"]) for r in dispatch.records()}
        assert ("nemotron_h", "conv", "pallas") in paths
        want, want_grads = jax.jit(jax.value_and_grad(lambda v: loss(v, False)))(variables)
    finally:
        dispatch.reset()
    assert abs(float(got) - float(want)) <= FLOAT32_GAP * abs(float(want))
    gaps = jax.tree.map(rel, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) <= FLOAT32_GAP, gaps


@pytest.mark.parametrize("axes", [{"data": 2}, {"data": 2, "tensor": 2}], ids="x".join)
def test_under_a_mesh_the_kernels_run_a_chip_s_rows(devices, axes):
    """A Mosaic call has no partitioning rule, so under an ambient mesh the
    kernels run inside ``shard_map``, rows over the batch axes and the taps
    whole: ``y`` and the three gradients are the unsharded ``jax.numpy`` form's,
    ``dw`` and ``db`` summed over the rows' chips."""
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    args, weights = conv_inputs(5, 4, 256, 1, jnp.float32)
    want = with_gradients(jnp_form, weights, jnp.float32)(*args)
    mesh = mesh_lib.create_mesh(axes, devices=devices[:int(np.prod(list(axes.values())))])
    dispatch.reset()
    try:
        with jax.sharding.set_mesh(mesh):
            fn = with_gradients(lambda *a: dispatch.conv_fn("hybrid_lm", True)(*a[:3], start=D_INNER, dtype=a[3]),
                                weights, jnp.float32)
            assert "tensor<2x256x304xf32>" in fn.lower(*args).as_text()  # a chip's two rows inside the manual region
            got = fn(*args)
        (rec,) = dispatch.records()
        assert rec["path"] == "pallas"
    finally:
        dispatch.reset()
    for name, g, w in zip(("y", "dx", "dw", "db"), got, want, strict=True):
        assert g.shape == w.shape and rel(g, w) <= FLOAT32_GAP, (name, rel(g, w))


def test_rows_that_do_not_split_keep_the_jnp_form(devices):
    """One row over ``data=2``: the kernels could only run whole on every chip,
    which a bare Mosaic call cannot; the record says so."""
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    (proj, w, b), _ = conv_inputs(6, 1, 256, 1, jnp.float32)
    dispatch.reset()
    try:
        with jax.sharding.set_mesh(mesh_lib.create_mesh({"data": 2}, devices=devices[:2])):
            y = jax.jit(lambda *a: dispatch.conv_fn("hybrid_lm", True)(*a, start=D_INNER, dtype=jnp.float32))(proj, w, b)
        (rec,) = dispatch.records()
        assert rec["path"] == "jnp" and "1 rows do not split over the batch axes of a mesh of 2 devices" in rec["reason"]
    finally:
        dispatch.reset()
    assert rel(y, jnp_form(proj, w, b, jnp.float32)) <= FLOAT32_GAP
