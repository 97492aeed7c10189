"""Loss functions.

Replaces the reference's criterion hook output (``example_trainer.py:55-58`` —
a closure over ``F.cross_entropy`` on raw logits). Losses always accumulate in
float32 even when activations are bfloat16, so bf16 training (BASELINE config 5)
keeps a stable loss scale without GradScaler machinery.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


@jax.named_scope("loss_head")
def tied_cross_entropy(
    hidden: jax.Array,
    embedding: jax.Array,
    targets: jax.Array,
    *,
    chunk_size: int = 8192,
) -> jax.Array:
    """Per-token NLL for a tied-embedding LM head WITHOUT materializing the
    full logits tensor.

    ``hidden``: ``[..., d]`` final hidden states; ``embedding``: ``[V, d]``
    (the tied token embedding); ``targets``: integer ids of exactly
    ``hidden``'s leading shape. Returns per-token NLL of that leading shape.
    Chunk logits are computed float32 (both operands upcast), matching the
    model's own ``x.astype(f32) @ E.T.astype(f32)`` head bit-for-bit in
    convention — FUSED_CE on/off runs stay numerically comparable.

    The naive path computes ``hidden @ embedding.T`` — ``[B, T, V]`` float32,
    13 GB for GPT-2-small at batch 64 / T 1024 (an observed single-chip OOM).
    Here the vocabulary is scanned in ``chunk_size`` slices with an online
    logsumexp, so peak memory is O(N * chunk_size); each chunk is wrapped in
    ``jax.checkpoint`` so the backward pass recomputes its logits instead of
    storing them.

    Every op of the head carries the ``loss_head`` scope in its name (HLO
    metadata only): the scan sits under ``jax.checkpoint``, so autodiff
    carries the scope into the backward's and the recomputation's op names
    too. The benchmark's ``loss_head_time_share`` matches it.
    """
    lead_shape = hidden.shape[:-1]
    d = hidden.shape[-1]
    v = embedding.shape[0]
    if targets.shape != lead_shape:
        raise ValueError(f"targets {targets.shape} must match hidden leading {lead_shape}")
    x = hidden.reshape(-1, d).astype(jnp.float32)
    tgt = targets.reshape(-1)
    n = x.shape[0]
    # Never chunk wider than the (lane-aligned) vocab: a small vocab under the
    # default chunk_size would otherwise pad 256 -> 8192 rows and compute 32x
    # the naive head's work.
    chunk_size = min(chunk_size, -(-v // 128) * 128)
    n_chunks = -(-v // chunk_size)
    v_pad = n_chunks * chunk_size
    emb = jnp.pad(embedding, ((0, v_pad - v), (0, 0))).reshape(n_chunks, chunk_size, d)

    @jax.checkpoint
    def chunk(carry, args):
        m, l, tgt_logit = carry
        emb_c, base = args
        # [N, C] logits for this vocab slice — f32 operands, matching the
        # model head's convention (see docstring).
        logits = jnp.einsum(
            "nd,cd->nc", x, emb_c.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        # padded vocab rows must not win the max or contribute to the sum
        col = base + jnp.arange(chunk_size)
        logits = jnp.where(col[None, :] < v, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=1))
        l = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(axis=1)
        in_chunk = (tgt >= base) & (tgt < base + chunk_size)
        local = jnp.clip(tgt - base, 0, chunk_size - 1)
        picked = jnp.take_along_axis(logits, local[:, None], axis=1)[:, 0]
        tgt_logit = jnp.where(in_chunk, picked, tgt_logit)
        return (m_new, l, tgt_logit), None

    init = (
        jnp.full((n,), -1e30, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    bases = jnp.arange(n_chunks) * chunk_size
    (m, l, tgt_logit), _ = jax.lax.scan(chunk, init, (emb, bases))
    nll = m + jnp.log(jnp.maximum(l, 1e-30)) - tgt_logit
    return nll.reshape(lead_shape)
