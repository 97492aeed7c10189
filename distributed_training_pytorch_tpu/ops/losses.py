"""Loss functions.

Replaces the reference's criterion hook output (``example_trainer.py:55-58`` —
a closure over ``F.cross_entropy`` on raw logits). Losses always accumulate in
float32 even when activations are bfloat16, so bf16 training (BASELINE config 5)
keeps a stable loss scale without GradScaler machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def softmax_cross_entropy_with_integer_labels(
    logits: jax.Array,
    labels: jax.Array,
    *,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Per-example stable softmax CE from integer labels. Returns shape [B]."""
    logits = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
    if label_smoothing:
        smooth = -log_probs.mean(axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return nll


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    *,
    label_smoothing: float = 0.0,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Mean CE over the (global) batch — under ``jit`` with a data-sharded
    batch this mean is computed collectively, so the reported loss is the
    *global* loss, fixing the reference's local-only reporting
    (``trainer/trainer.py:175-178``).

    ``weights`` (shape [B], e.g. the loader's pad ``mask``) turns the mean into
    a weighted mean so padded rows contribute nothing."""
    nll = softmax_cross_entropy_with_integer_labels(
        logits, labels, label_smoothing=label_smoothing
    )
    return weighted_mean(nll, weights)


def weighted_mean(values: jax.Array, weights: jax.Array | None = None) -> jax.Array:
    """Mean of per-example values, optionally weighted (pad-mask aware).
    An all-zero weight vector yields 0, not NaN; fractional weights divide by
    their true sum."""
    values = values.astype(jnp.float32)
    if weights is None:
        return values.mean()
    weights = weights.astype(jnp.float32)
    total = weights.sum()
    return jnp.where(total > 0, (values * weights).sum() / jnp.maximum(total, 1e-8), 0.0)


# One slice's float32 logits on one chip, [rows, slice, V], the head's only
# large temporary, stay under this: 4,096 tokens a slice at V = 50,257
# (PERF.md §6, PR 29, has the sweep on the chip that chose it).
_SLICE_LOGITS_BYTES = 1 << 30


def tied_cross_entropy_loss(
    hidden: jax.Array,
    embedding: jax.Array,
    targets: jax.Array,
    weights: jax.Array | None = None,
    positions: jax.Array | None = None,
    scope: str = "loss_head",
) -> jax.Array:
    """Mean next-token NLL of a tied-embedding LM head WITHOUT materializing
    the ``[B, T, V]`` logits (13 GB float32 for GPT-2-small at batch 64 /
    T 1024: an observed single-chip OOM), and with its gradients taken in the
    forward pass.

    ``hidden``: ``[B, T, d]`` final hidden states; ``embedding``: ``[V, d]``
    (the tied token embedding); ``targets``: ``[B, T]`` integer ids;
    ``weights``: optional ``[B]`` per-example weights (the loader's pad
    ``mask``; not differentiated); ``positions``: optional ``[T]`` 0 / 1, the
    positions a row's mean is taken over (a prediction two tokens ahead has no
    target at the last position). Returns the scalar
    ``weighted_mean(nll.mean(-1), weights)``, 0 under all-zero weights.

    The sequence is scanned a slice at a time (the length comes from the
    shapes: :func:`_slice_len`). A slice sees whole rows of the vocabulary, so
    its softmax is exact in one pass and the logits' gradient is known where
    the logits are: under differentiation a slice is three matmuls (logits,
    the hidden states' gradient, the embedding's gradient added into a
    float32 carry), none computed twice and no vocabulary column padded. The
    head ends in a scalar, so the backward pass only multiplies those two
    gradients by the scalar cotangent (a loss scale arrives there). Without
    differentiation a slice is the logits matmul alone. Logits are float32
    from the operands as they arrive (bf16 under the policy; float32
    parameters beside a bf16 model are rounded by the MXU's one
    default-precision pass), the model head's own convention, so FUSED_CE
    on/off stay comparable.

    Every op, the backward's scaling included, carries the ``loss_head``
    scope in its name (HLO metadata only): the benchmark's
    ``loss_head_time_share`` matches it. A second head pass names its own
    (``scope``: the multi-token-prediction module's is ``mtp_loss_head``):
    the scope of a call site does not reach inside the custom VJP.
    """
    if hidden.ndim != 3 or targets.shape != hidden.shape[:2]:
        raise ValueError(f"want hidden [B, T, d] and targets [B, T], got {hidden.shape}, {targets.shape}")
    b, t = targets.shape
    with jax.named_scope(scope):
        if weights is None:
            coef = jnp.full((b,), 1.0 / (b * t), jnp.float32)
        else:
            # weighted_mean(nll.mean(-1), weights) as one factor a row
            w = jax.lax.stop_gradient(weights).astype(jnp.float32)
            total = w.sum()
            coef = jnp.where(total > 0, w / (jnp.maximum(total, 1e-8) * t), 0.0)
        if positions is not None:  # a factor a position: [B, T], each row's mean over the positions kept
            kept = jax.lax.stop_gradient(positions).astype(jnp.float32)
            coef = coef[:, None] * (kept * t / jnp.maximum(kept.sum(), 1.0))[None, :]
    return _tied_head(hidden, embedding, targets, coef, (hidden.dtype, embedding.dtype), scope)


def _slice_len(rows: int, seq_len: int, vocab: int) -> int:
    """Longest divisor of ``seq_len`` whose float32 logits ``[rows, slice,
    vocab]`` (``rows`` a chip's) fit ``_SLICE_LOGITS_BYTES``; 1 where none does."""
    fits = _SLICE_LOGITS_BYTES // (4 * rows * vocab)
    return max([s for s in range(1, min(fits, seq_len) + 1) if seq_len % s == 0], default=1)


def _head_scan(hidden, embedding, targets, coef, *, with_grads: bool):
    """``sum(coef[b] * nll[b, t])`` and, ``with_grads``, its float32 gradients
    by ``hidden`` and ``embedding``, a slice of the sequence at a time.

    The batch is laid out ``[chips, rows a chip]`` along the mesh's batch
    axes and ``chips`` is a batch dimension of every matmul, the embedding
    gradient's included: each chip carries its own partial sum through the
    loop, and the one sum over ``chips`` after it is the partitioner's one
    exchange (on one chip the axis has length 1). ``coef`` is a factor a row
    ``[B]`` or a factor a token ``[B, T]``, which the scan takes a slice at a
    time beside the tokens."""
    from distributed_training_pytorch_tpu.parallel.mesh import ambient_batch_axes

    b, t, d = hidden.shape
    v = embedding.shape[0]
    axes, c = ambient_batch_axes(b)
    r = b // c
    s = _slice_len(r, t, v)
    # [T/s, chips, rows, s, ...]: the scan runs over slices of T
    h_slices = jnp.moveaxis(hidden.reshape(c, r, t // s, s, d), 2, 0)
    t_slices = jnp.moveaxis(targets.reshape(c, r, t // s, s), 2, 0)
    xs = (h_slices, t_slices)
    if coef.ndim == 2:
        xs += (jnp.moveaxis(coef.reshape(c, r, t // s, s), 2, 0),)
    else:
        coef = coef.reshape(c, r, 1)

    def one_slice(carry, xs):
        h, tgt, *by_token = xs
        coef_s = by_token[0] if by_token else coef
        # float32 logits from the operands as they arrive: one MXU pass
        logits = jnp.einsum("crsd,vd->crsv", h, embedding, preferred_element_type=jnp.float32)
        m = logits.max(axis=-1)
        p = jnp.exp(logits - m[..., None])
        l = p.sum(axis=-1)
        picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        loss = ((m + jnp.log(l) - picked) * coef_s).sum(axis=(1, 2))
        if not with_grads:
            return carry + loss, None
        loss_sum, d_emb = carry
        hit = jnp.arange(v) == tgt[..., None]
        d_logits = (p / l[..., None] - hit) * coef_s[..., None]
        # float32 operands at the default precision: one bf16 MXU pass on the
        # chip, exact on the CPU (what autodiff of the logits matmul gives)
        d_h = jnp.einsum("crsv,vd->crsd", d_logits, embedding.astype(jnp.float32))
        d_emb = d_emb + jnp.einsum("crsv,crsd->cvd", d_logits, h.astype(jnp.float32))
        return (loss_sum + loss, d_emb), d_h

    def per_chip(shape):
        zeros = jnp.zeros(shape, jnp.float32)
        return jax.lax.with_sharding_constraint(zeros, P(axes)) if axes else zeros

    if not with_grads:
        return jax.lax.scan(one_slice, per_chip((c,)), xs)[0].sum()
    (loss, d_emb), d_h = jax.lax.scan(
        one_slice, (per_chip((c,)), per_chip((c, v, d))), xs
    )
    return loss.sum(), jnp.moveaxis(d_h, 0, 2).reshape(b, t, d), d_emb.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tied_head(hidden, embedding, targets, coef, dtypes, scope):
    """``dtypes`` = (hidden's, embedding's): what the backward casts to."""
    with jax.named_scope(scope):
        return _head_scan(hidden, embedding, targets, coef, with_grads=False)


def _tied_head_fwd(hidden, embedding, targets, coef, dtypes, scope):
    with jax.named_scope(scope):
        loss, d_hidden, d_emb = _head_scan(hidden, embedding, targets, coef, with_grads=True)
    return loss, (d_hidden, d_emb)


def _tied_head_bwd(dtypes, scope, res, g):
    d_hidden, d_emb = res
    # scaled in float32 before the cast: under fp16 the unscaled gradients
    # would underflow, which is what a loss scale is there to prevent
    with jax.named_scope(scope):
        return (d_hidden * g).astype(dtypes[0]), (d_emb * g).astype(dtypes[1]), None, None


_tied_head.defvjp(_tied_head_fwd, _tied_head_bwd)
