"""Parity tests for the Pallas flash-attention kernel (ops/pallas.py).

Runs the real kernel logic through the Pallas interpreter on the CPU test
platform (strict float32 tolerances; on TPU the MXU's bf16 multiply path adds
~1e-3 noise to both sides, checked separately in the bench toggle). Reference:
the plain O(T^2) softmax attention in ``models/vit.py``.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.ops.pallas import flash_attention
from distributed_training_pytorch_tpu.models.vit import (
    MultiHeadAttention,
    default_attention_fn,
)


def reference_attention(q, k, v, causal):
    """Plain softmax attention; ``Tq != Tk`` allowed (row r sees column c iff
    r >= c, both from 0 — the kernels' convention)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = np.arange(q.shape[1])[:, None] >= np.arange(k.shape[1])[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


# (b, t, h, d, causal, block_q, block_k); None = the shape rule's blocks,
# which clamp to a single block at these T.
CASES = [
    (2, 197, 3, 64, False, None, None),  # ViT-B/16 sequence length (197 = 14^2 + cls)
    (1, 256, 2, 32, False, None, None),  # block-aligned
    (2, 100, 2, 16, True, None, None),  # causal, unaligned T
    (1, 130, 4, 64, True, None, None),  # causal, crosses one block boundary
    # Several blocks on each side: the loop bounds and the masked / unmasked
    # split are exercised (ISSUE 26).
    (1, 512, 2, 32, True, 128, 128),  # 4 x 4, 6 pairs skipped
    (1, 384, 2, 32, True, 256, 128),  # bq > bk: the k-loop's upper bound
    (1, 384, 2, 32, True, 128, 256),  # bq < bk: the q-loop's lower bound
    (1, 300, 2, 32, True, 128, 128),  # padding and diagonal in the same last block
    (1, 300, 2, 32, False, 128, 128),  # non-causal: every block, the last one padded
    # Causal, one block pair: walked as static sub-tiles, the ones above the
    # diagonal left out (ISSUE 32). The rule gives the forward two row groups
    # and the backward sub-tiles of 128 where the block splits so.
    (1, 256, 2, 32, True, None, None),  # 2 x 2 of 128 in both kernels
    (1, 512, 2, 32, True, None, None),  # forward 2 x 2 of 256, backward 4 x 4 of 128
    (1, 1024, 1, 64, True, None, None),  # the LM cells' T: forward 2 x 2 of 512, backward 8 x 8
    (1, 700, 2, 64, True, None, None),  # padded into 768: 2 x 2 of 384, the last one padded
    (2, 512, 2, 64, True, 512, 512),  # a caller's one block: the same rule
]
# The fused backward (ISSUE 30): dq is summed over the grid's k-block axis in
# a scratch slab where that axis has several blocks, written straight out
# where it has one.
BWD_CASES = [
    (1, 520, 2, 32, True, 256, 128),  # bq != bk, T a multiple of neither, 5 k-blocks
    (2, 384, 2, 32, False, 256, 128),  # non-causal, bq != bk: every row of the slab from every k-block
    (1, 512, 2, 64, True, 128, 512),  # one k-block, four q-blocks: no scratch, rows stored from the loop
    (1, 512, 2, 64, True, 512, 128),  # one q-block, four k-blocks: the whole slab added to each grid step
]
GRAD_CASES = CASES[:1] + CASES[2:3] + CASES[4:] + BWD_CASES


@pytest.mark.parametrize("b,t,h,d,causal,block_q,block_k", CASES)
def test_forward_parity(b, t, h, d, causal, block_q, block_k):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,t,h,d,causal,block_q,block_k", GRAD_CASES)
def test_gradient_parity(b, t, h, d, causal, block_q, block_k):
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.float32) for _ in range(3))
    cotangent = jnp.cos(jnp.arange(b * t * h * d, dtype=jnp.float32)).reshape(b, t, h, d) * 0.1

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
        return jnp.sum(out * cotangent)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal) * cotangent)

    grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(grads_flash, grads_ref, "qkv", strict=True):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=2e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize(
    "t,blk,keep",
    [
        (512, 128, 384),  # 4 x 4 block pairs: the loops' bounds
        # One block pair walked as sub-tiles (ISSUE 32): the forward's last
        # column group NaN (the backward's last two, and its last four).
        (512, None, 256),
        (1024, None, 512),
    ],
)
def test_causal_skip_never_reads_future_blocks(t, blk, keep):
    """The test that the skip engages: with the last k-block's (or the last
    sub-tile's) k and v rows NaN, a kernel that computes the blocks above the
    diagonal and masks them afterwards poisons every row (0 x NaN in p @ v,
    NaN in q @ k^T; 0 x NaN in ds, so in every row of dq); one that never
    visits them leaves all earlier q-blocks exactly as if the sequence ended
    before the NaNs."""
    rng = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(rng.randn(1, t, 2, 32), jnp.float32) for _ in range(4))
    k_nan = k.at[:, keep:].set(jnp.nan)
    v_nan = v.at[:, keep:].set(jnp.nan)
    g = g.at[:, keep:].set(0.0)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk)

    out, vjp = jax.vjp(flash, q, k_nan, v_nan)
    dq, _, _ = vjp(g)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: reference_attention(q, k, v, True), q[:, :keep], k[:, :keep], v[:, :keep]
    )
    ref_dq, _, _ = ref_vjp(g[:, :keep])
    assert np.isfinite(np.asarray(out[:, :keep])).all()
    assert np.isfinite(np.asarray(dq[:, :keep])).all()
    np.testing.assert_allclose(np.asarray(out[:, :keep]), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(dq[:, :keep]), np.asarray(ref_dq), atol=2e-4)


@pytest.mark.parametrize(
    "tq,tk,causal,block_q,block_k",
    [
        (384, 384, True, 128, 128),
        (256, 384, True, 128, 128),
        # The fused backward (ISSUE 30) under the ring's shapes:
        (384, 256, True, 256, 128),  # more rows than columns, bq != bk
        (256, 384, True, 128, 256),  # a k-block seen by no row still closes dq's sum
        (256, 384, False, 128, 128),  # a visible block: non-causal, Tq != Tk
        (200, 300, False, 128, 128),  # neither side a multiple of its block
        # One block a side, causal: static sub-tiles (ISSUE 32), Tq != Tk both ways.
        (256, 512, True, None, None),  # a column group no row sees: dk / dv zero, dq closed
        (512, 256, True, None, None),  # a row group past every column: no diagonal sub-tile in it
        (128, 512, True, None, None),  # the forward splits in four: 128 does not hold a half of 512
        (200, 500, True, None, None),  # both sides padded into their blocks
        (500, 200, True, None, None),  # padded keys in columns every row of the last group sees
    ],
)
def test_flash_block_entry_points_causal_multi_block(tq, tk, causal, block_q, block_k):
    """``flash_block_fwd`` / ``flash_block_bwd`` — the ring path's per-block
    passes — against the reference with no mesh: causal (the diagonal block)
    and not (a visible one), several blocks a side or one walked as sub-tiles,
    square and ``Tq != Tk`` (where the last k-block is seen by no row: its
    dk / dv are exactly 0)."""
    from distributed_training_pytorch_tpu.ops.pallas import flash_block_bwd, flash_block_fwd

    rng = np.random.RandomState(8)
    q, g = (jnp.asarray(rng.randn(1, tq, 2, 32), jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, tk, 2, 32), jnp.float32) for _ in range(2))
    blocks = dict(causal=causal, block_q=block_q, block_k=block_k, interpret=True)

    o, lse = flash_block_fwd(q, k, v, **blocks)
    ref, ref_vjp = jax.vjp(lambda q, k, v: reference_attention(q, k, v, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :] if causal else np.ones((tq, tk), bool)
    ref_lse = jax.nn.logsumexp(jnp.where(mask, logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)

    delta = jnp.einsum("bqhd,bqhd->bhq", g, o)
    grads = flash_block_bwd(q, k, v, g, lse, delta, **blocks)
    for got, want, name in zip(grads, ref_vjp(g), "qkv", strict=True):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, err_msg=f"d{name}"
        )
    if causal and tq < tk:
        assert not np.asarray(grads[1][:, tq:]).any() and not np.asarray(grads[2][:, tq:]).any()


def test_block_counts_match_a_brute_force_count_and_reach_the_record():
    """``flash_block_counts`` (which shares its bound with the kernels' loops)
    == the number of block pairs holding at least one unmasked element, and
    the flash ``kernel_dispatch`` record carries the plan."""
    from distributed_training_pytorch_tpu.ops import dispatch
    from distributed_training_pytorch_tpu.ops.pallas import flash_block_counts, flash_block_plan

    sizes, blocks = (128, 300, 384, 512, 1024), (128, 256, 512)
    for t_q, t_k, bq, bk in itertools.product(sizes, sizes, blocks, blocks):
        n_q, n_k = -(-t_q // bq), -(-t_k // bk)
        r, c = np.arange(n_q * bq)[:, None], np.arange(n_k * bk)[None, :]
        seen = (r >= c).reshape(n_q, bq, n_k, bk).any(axis=(1, 3))
        assert flash_block_counts(t_q, t_k, bq, bk, True) == (n_q * n_k, seen.sum())
        assert flash_block_counts(t_q, t_k, bq, bk, False) == (n_q * n_k, n_q * n_k)
    assert flash_block_counts(4096, 4096, 1024, 1024, True) == (16, 10)
    assert flash_block_counts(4096, 4096, 256, 256, True) == (256, 136)

    dispatch.reset()
    try:
        fn = dispatch.attention_fn("transformer_lm", True, causal=True, block_q=128, block_k=128)
        x = jnp.zeros((1, 512, 1, 8), jnp.float32)
        jax.eval_shape(fn, x, x, x)
        (rec,) = [r for r in dispatch.records() if r["path"] == "flash"]
        # The LM cells' call: one block pair, counted at the sub-tiles the kernels skip at.
        dispatch.reset()  # decisions are recorded once a (model, path, reason)
        fn = dispatch.attention_fn("transformer_lm", True, causal=True)
        x = jnp.zeros((1, 1024, 1, 8), jnp.float32)
        jax.eval_shape(fn, x, x, x)
        (rec_1024,) = [r for r in dispatch.records() if r["path"] == "flash"]
    finally:
        dispatch.reset()
    plan = flash_block_plan(512, 512, True, 128, 128)
    assert plan == {"block_q": 128, "block_k": 128, "sub_block": None,
                    "blocks_total": 16, "blocks_computed": 10,
                    "backward": "fused", "bwd_block_q": 128, "bwd_block_k": 128,
                    "bwd_sub_block": None}
    assert {k: rec[k] for k in plan} == plan
    subs = (rec_1024["block_q"], rec_1024["sub_block"], rec_1024["bwd_sub_block"])
    assert subs == (1024, 512, 128)
    assert (rec_1024["blocks_total"], rec_1024["blocks_computed"]) == (4, 3)


def _pallas_calls(jaxpr):
    """Names of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_calls(sub)
    return names


@pytest.mark.parametrize(
    "t,causal,valid_len",
    [
        (1024, True, None),  # gpt2s_t1024: one block pair
        (4096, True, None),  # gpt2s_t4096: the shape the claim rests on
        (8192, True, None),  # the longest T the forward compiles at
        (256, False, 197),  # ViT-B's 197 in 256
    ],
)
def test_backward_is_one_pallas_call(t, causal, valid_len):
    """Traced, not run: the custom VJP's backward holds one ``pallas_call``
    (``flash_dqkv``: dq, dk and dv from one pass over the block pairs) at
    every T the forward compiles at, head dim 64; its name holds ``flash_dq``,
    which ``benchmarks/metrics/flash_bwd_time_share.py`` matches."""
    x = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.bfloat16)

    def bwd(q, k, v, g):
        flash = lambda q, k, v: flash_attention(q, k, v, causal=causal, valid_len=valid_len)  # noqa: E731
        return jax.vjp(flash, q, k, v)[1](g)

    calls = _pallas_calls(jax.make_jaxpr(bwd)(x, x, x, x).jaxpr)
    (backward,) = [name for name in calls if name != "flash_fwd"]  # besides the vjp's forward
    assert calls.count("flash_fwd") == 1 and backward == "flash_dqkv", calls
    assert any(pattern in backward for pattern in ("flash_dq", "flash_dkv"))  # the benchmark reader's two


@pytest.mark.parametrize(
    "t_q,t_k,causal,want,want_sub,want_counts",
    [
        (1024, 1024, True, (1024, 1024), (512, 128), (4, 3)),  # the LM cells: sub-tiles of one pair
        (4096, 4096, True, (512, 512), (None, None), (64, 36)),  # the benchmark's long cell
        (8192, 8192, True, (1024, 1024), (None, None), (256, 136)),  # the longest T that compiles
        (256, 256, False, (256, 256), (None, None), (1, 1)),  # clamped to T; non-causal: one tile
        (2048, 512, False, (1024, 512), (None, None), (2, 2)),  # a ring shard against a visiting block
        (512, 512, True, (512, 512), (256, 128), (4, 3)),
        (700, 700, True, (768, 768), (384, 384), (4, 3)),  # 768 halves into 128-multiples, no further
        (256, 512, True, (256, 512), (256, 128), (2, 1)),  # the ring's diagonal block, Tq < Tk
        (128, 512, True, (128, 512), (128, 128), (4, 1)),  # forward in four: 128 holds no half of 512
        (300, 300, True, (384, 384), (None, None), (1, 1)),  # 384 / 2, 4, 8: no 128-multiple
        (100, 100, True, (128, 128), (None, None), (1, 1)),  # one lane tile: nothing to split
    ],
)
def test_block_plan_carries_the_backward(t_q, t_k, causal, want, want_sub, want_counts):
    """``flash_block_plan`` (spread into the ``kernel_dispatch`` record) says
    which backward the shapes get and at which block shape, the sub-tile size
    each kernel walks a one-block causal call in (None: one tile), and the
    forward's block pairs at the granularity it skips at."""
    from distributed_training_pytorch_tpu.ops.pallas import flash_block_plan

    plan = flash_block_plan(t_q, t_k, causal)
    assert plan["backward"] == "fused"
    assert (plan["bwd_block_q"], plan["bwd_block_k"]) == want
    assert (plan["sub_block"], plan["bwd_sub_block"]) == want_sub
    assert (plan["blocks_total"], plan["blocks_computed"]) == want_counts


def test_default_attention_fn_selects_by_backend():
    # CPU test platform: auto mode must fall back to plain XLA attention.
    assert default_attention_fn(None) is None
    assert default_attention_fn(False) is None
    assert default_attention_fn(True) is not None


def test_mha_with_flash_kernel_matches_plain():
    """MultiHeadAttention with the kernel plugged into attention_fn matches
    the default path (same params)."""
    from distributed_training_pytorch_tpu.ops.pallas import make_attention_fn

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 50, 32), jnp.float32)
    plain = MultiHeadAttention(num_heads=4)
    # min_seq_len=1 forces the kernel path even at T=50 (the default adapter
    # would route short sequences to the plain implementation).
    flash = MultiHeadAttention(num_heads=4, attention_fn=make_attention_fn(min_seq_len=1))
    variables = plain.init(jax.random.key(0), x)
    out_plain = plain.apply(variables, x)
    out_flash = flash.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_plain), atol=2e-5)


@pytest.mark.parametrize(
    "t,valid,block",
    [
        (24, 17, None),
        (256, 197, None),  # ViT-B's 197 in pad_seq_to=256: one block pair
        (300, 197, 128),  # several blocks: valid_len ends inside the second k-block, the third is all padding
    ],
)
def test_flash_valid_len_matches_masked_plain(t, valid, block):
    """valid_len (caller-padded sequences) masks exactly like the plain
    path's key mask — outputs AND gradients, through the custom VJP."""
    from distributed_training_pytorch_tpu.models.vit import dot_product_attention
    from distributed_training_pytorch_tpu.ops.pallas import flash_attention

    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, t, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, t, 4, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, t, 4, 8), jnp.float32)

    def f_flash(q, k, v):
        return flash_attention(
            q, k, v, valid_len=valid, block_q=block, block_k=block, interpret=True
        )

    def f_plain(q, k, v):
        return dot_product_attention(q, k, v, valid_len=valid)

    out_f, out_p = f_flash(q, k, v), f_plain(q, k, v)
    # Rows past valid_len are inert padding — compare the real rows.
    np.testing.assert_allclose(
        np.asarray(out_f[:, :valid]), np.asarray(out_p[:, :valid]), atol=2e-5
    )
    # Gradient parity with upstream grads zeroed on pad rows (what a model
    # whose loss ignores pad rows produces).
    g = jnp.asarray(rng.randn(2, t, 4, 8), jnp.float32).at[:, valid:].set(0.0)
    gf = jax.grad(lambda *a: jnp.vdot(f_flash(*a), g), argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(lambda *a: jnp.vdot(f_plain(*a), g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gp, strict=True):
        np.testing.assert_allclose(
            np.asarray(a[:, :valid]), np.asarray(b[:, :valid]), atol=3e-5
        )


@pytest.mark.slow
def test_vit_pad_seq_to_exact_semantics():
    """pad_seq_to changes tiling, not math: same params, same logits and
    same parameter gradients as the unpadded model."""
    from distributed_training_pytorch_tpu.models.vit import ViTTiny

    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32)
    base = ViTTiny(num_classes=3)            # T = 16 patches + cls = 17
    padded = ViTTiny(num_classes=3, pad_seq_to=24)
    variables = base.init(jax.random.key(0), x)
    np.testing.assert_allclose(
        np.asarray(padded.apply(variables, x)),
        np.asarray(base.apply(variables, x)),
        atol=2e-5,
    )

    def loss(v, m):
        return jnp.sum(m.apply(v, x) ** 2)

    gb = jax.grad(loss)(variables, base)
    gp = jax.grad(loss)(variables, padded)
    for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(gp), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# --------------------------------------------------------------------------
# Fused 1x1-conv + BN-apply + ReLU GEMM kernel


def test_conv1x1_bn_act_matches_xla():
    """Kernel == relu((x @ w) * a + b) exactly (f32), incl. row padding."""
    from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 7, 5, 24), jnp.float32)  # 70 rows: pads to 32k
    w = jnp.asarray(rng.randn(24, 16) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)
    got = conv1x1_bn_act(x, w, a, b, interpret=True, block_rows=32)
    ref = jnp.maximum((x.reshape(-1, 24) @ w) * a + b, 0.0).reshape(2, 7, 5, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    # relu=False epilogue
    got = conv1x1_bn_act(x, w, a, b, relu=False, interpret=True, block_rows=32)
    ref = ((x.reshape(-1, 24) @ w) * a + b).reshape(2, 7, 5, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_conv1x1_bn_act_tiles_cout_when_the_weight_slab_would_not_fit(monkeypatch):
    """Cout is tiled only where the double-buffered block buffers exceed
    the VMEM budget (ConvNeXt-L's 1536 -> 6144 is refused whole on the chip);
    the tiled grid computes the same thing, and callers whose buffers fit
    keep Cout whole."""
    from distributed_training_pytorch_tpu.ops import pallas as plmod

    # The shapes chip_smoke.py compiles (bf16, block_rows 1024): only the two
    # widest ConvNeXt-L expands are tiled.
    tiles = {
        (cin, cout): plmod._conv1x1_block_cols(1024, cin, cout, 2, 2, 2)
        for cin, cout in ((64, 256), (256, 64), (192, 768), (384, 1536),
                          (768, 3072), (1536, 6144))
    }
    assert tiles.pop((1536, 6144)) == 512
    assert tiles.pop((768, 3072)) == 1024
    assert all(bn == cout for (_, cout), bn in tiles.items())

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(3, 5, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 384) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(384) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(384), jnp.float32)
    # 2 * (x 1536 B + weight tile 24*bn*4 + out tile 16*bn*4): 131 kB whole,
    # 44 kB at bn=128.
    monkeypatch.setattr(plmod, "_CONV1X1_VMEM_BUDGET", 50_000)
    assert plmod._conv1x1_block_cols(16, 24, 384, 4, 4, 4) == 128
    got = plmod.conv1x1_bn_act(x, w, a, b, act="gelu", interpret=True, block_rows=16)
    ref = jax.nn.gelu((x.reshape(-1, 24) @ w) * a + b, approximate=True).reshape(3, 5, 384)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_conv1x1_bn_act_diff_gradients():
    """Custom VJP (Pallas fwd, XLA-dot bwd) == autodiff of the reference for
    every operand, relu on and off."""
    from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act_diff

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(48, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 16) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)
    for relu in (True, False):
        def f(x, w, a, b, relu=relu):
            return jnp.sum(
                conv1x1_bn_act_diff(x, w, a, b, relu=relu, interpret=True, block_rows=16)
                ** 2
            )

        def ref(x, w, a, b, relu=relu):
            y = (x @ w) * a + b
            if relu:
                y = jnp.maximum(y, 0.0)
            return jnp.sum(y**2)

        gp = jax.grad(f, argnums=(0, 1, 2, 3))(x, w, a, b)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, w, a, b)
        for p, r, name in zip(gp, gr, ("x", "w", "scale", "bias"), strict=True):
            np.testing.assert_allclose(
                np.asarray(p), np.asarray(r), atol=2e-4,
                err_msg=f"d{name} relu={relu}",
            )


def test_conv1x1_bn_act_gelu_epilogue_matches_reference():
    """act="gelu" (the ConvNeXt expand-Dense epilogue, ISSUE 17) == tanh-
    approx gelu((x @ w) * a + b) — the same approximation flax's nn.gelu
    defaults to, so the fused path matches the plain Dense+gelu program."""
    from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 5, 3, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 16) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)
    got = conv1x1_bn_act(x, w, a, b, act="gelu", interpret=True, block_rows=32)
    ref = jax.nn.gelu((x.reshape(-1, 24) @ w) * a + b, approximate=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.reshape(2, 5, 3, 16)), atol=1e-5
    )


def test_conv1x1_bn_act_diff_gelu_gradients():
    """Backward parity for the gelu epilogue: the custom VJP's z-recompute +
    jax.vjp gelu backward == autodiff of the plain reference, all operands."""
    from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act_diff

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(48, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 16) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)

    def f(x, w, a, b):
        return jnp.sum(
            conv1x1_bn_act_diff(
                x, w, a, b, act="gelu", interpret=True, block_rows=16
            ) ** 2
        )

    def ref(x, w, a, b):
        return jnp.sum(jax.nn.gelu((x @ w) * a + b, approximate=True) ** 2)

    gp = jax.grad(f, argnums=(0, 1, 2, 3))(x, w, a, b)
    gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, w, a, b)
    for p, r, name in zip(gp, gr, ("x", "w", "scale", "bias"), strict=True):
        np.testing.assert_allclose(
            np.asarray(p), np.asarray(r), atol=2e-4, err_msg=f"d{name} gelu"
        )


def test_chained_window_parity_fused_vs_plain():
    """The chained-window program (the shape bench.py/autotune actually
    time): a lax.scan whose carry feeds the next trip's input must agree
    between the fused kernel and the plain path — values AND gradients
    survive the scan's repeated VJP."""
    from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act_diff

    rng = np.random.RandomState(5)
    x0 = jnp.asarray(rng.randn(32, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 16) * 0.2, jnp.float32)
    a = jnp.asarray(rng.rand(16) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)

    def chain(apply, x0, w):
        def body(x, _):
            y = apply(x, w)
            return 0.5 * y + 0.5 * x, jnp.sum(y)
        return jax.lax.scan(body, x0, None, length=4)

    def fused(x, w):
        return conv1x1_bn_act_diff(x, w, a, b, interpret=True, block_rows=16)

    def plain(x, w):
        return jnp.maximum((x @ w) * a + b, 0.0)

    (cf, sf), (cp, sp) = chain(fused, x0, w), chain(plain, x0, w)
    np.testing.assert_allclose(np.asarray(cf), np.asarray(cp), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sp), rtol=2e-6)
    gf = jax.grad(lambda w: jnp.sum(chain(fused, x0, w)[0] ** 2))(w)
    gp = jax.grad(lambda w: jnp.sum(chain(plain, x0, w)[0] ** 2))(w)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gp), atol=5e-4)


def test_pallas_conv1x1_module_matches_nn_conv(monkeypatch):
    """models.resnet.PallasConv1x1 == nn.Conv 1x1 with the same kernel, for
    stride 1 and the strided-projection case."""
    from flax import linen as nn

    import distributed_training_pytorch_tpu.ops.pallas as plmod
    from distributed_training_pytorch_tpu.models.resnet import PallasConv1x1

    orig = plmod.conv1x1_bn_act_diff
    monkeypatch.setattr(
        plmod, "conv1x1_bn_act_diff",
        lambda *a, **k: orig(*a, **{**k, "interpret": True, "block_rows": 32}),
    )
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 8, 8, 12), jnp.float32)
    for strides in (1, 2):
        m = PallasConv1x1(10, strides=strides)
        v = m.init(jax.random.key(0), x)
        assert v["params"]["kernel"].shape == (1, 1, 12, 10)  # nn.Conv layout
        y = m.apply(v, x)
        ref = nn.Conv(10, (1, 1), strides=(strides, strides), use_bias=False).apply(
            {"params": {"kernel": v["params"]["kernel"]}}, x
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
