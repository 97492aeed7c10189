"""The state-space layer's two sequence operations, in ``jax.numpy``: the
chunked (dual) form of the selective scan of Mamba-2 (Dao & Gu 2024,
arXiv:2405.21060, "SSD") and the causal depthwise convolution in front of it.

The recurrence, per batch row and head (``x_t`` of ``P`` channels, ``B_t`` and
``C_t`` of ``N`` states shared by every head, ``Δ_t > 0`` and ``A < 0``
scalars a head), with ``S_0 = 0``::

    S_t = exp(Δ_t A) · S_{t-1} + Δ_t · x_t B_tᵀ        (S: [P, N])
    y_t = S_t C_t

:func:`ssd_chunked` computes the same ``y`` a chunk of ``Q`` steps at a time.
With ``a_t = Δ_t A`` and ``cs`` its running sum inside a chunk, a chunk's
output is ``(L ⊙ (C Bᵀ)) (Δ ⊙ x)`` with ``L_ts = exp(cs_t − cs_s)`` for
``s ≤ t`` (the steps of its own chunk) plus ``exp(cs_t) · C_t S_prev`` (what
the state entering the chunk still contributes); the states at the chunks'
ends are carried from chunk to chunk by a scan of ``T / Q`` steps. Every
product over a chunk is a matmul, so the work lands on the MXU; operands go
in as ``dtype`` (bfloat16 under the model's policy) and accumulate in
float32, while ``Δ A``, its running sums, every ``exp`` and the carried state
stay float32. The gradient is jax's own through these operations: a Pallas
kernel with a written backward is a later PR's (PERF.md §7), and the scope
``ssd_scan`` the caller wraps this in is what the benchmark's
``ssd_time_share`` / ``ssd_roofline`` read either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d", "ssd_chunked"]


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """Depthwise convolution over time that sees no future step:
    ``y_t = b + Σ_j w_j ⊙ x_{t-K+1+j}`` with zeros before the sequence.

    ``x``: ``[B, T, C]``; ``w``: ``[K, C]`` (tap ``K-1`` meets the current
    step); ``b``: ``[C]`` or None. Float32 inside and out: ``K`` shifted
    multiply-adds that the compiler fuses into one pass over ``x``."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = sum(w[j] * padded[:, j:j + t] for j in range(k))
    return y if b is None else y + b.astype(jnp.float32)


def ssd_chunked(x, dt, a, b, c, *, chunk: int, dtype=None) -> jax.Array:
    """``y`` of the recurrence above (no ``D`` skip), float32.

    ``x``: ``[B, T, H, P]``; ``dt``: ``[B, T, H]`` (after its softplus);
    ``a``: ``[H]`` (negative); ``b``, ``c``: ``[B, T, N]``. ``chunk``: steps a
    chunk; a ``T`` that is no multiple of it is padded with steps of ``Δ = 0``,
    which neither decay the state nor add to it. ``dtype``: the matmuls'
    operand type (``x``'s if None)."""
    dtype = dtype or x.dtype
    rows, t, h, p = x.shape
    n = b.shape[-1]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    # heads beside the batch axes, so that every product below is a batched matmul
    da = (dt * a.astype(f32)).reshape(rows, nc, chunk, h).transpose(0, 1, 3, 2)  # [B, nc, H, Q]
    cs = jnp.cumsum(da, axis=-1)
    xd = (x.astype(f32) * dt[..., None]).reshape(rows, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)  # Δ ⊙ x: [B, nc, H, Q, P]
    b = b.reshape(rows, nc, chunk, n).astype(dtype)
    c = c.reshape(rows, nc, chunk, n).astype(dtype)

    # Inside a chunk: (L ⊙ (C Bᵀ)) (Δ ⊙ x). The mask goes on before the exp:
    # above the diagonal cs_t − cs_s is positive and may overflow.
    seg = cs[..., :, None] - cs[..., None, :]  # [B, nc, H, Q(t), Q(s)]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctn,bcsn->bcts", c, b, preferred_element_type=f32)
    y = jnp.einsum("bchts,bchsp->bchtp", (decay * cb[:, :, None]).astype(dtype), xd.astype(dtype),
                   preferred_element_type=f32)

    # Each chunk's own contribution to the state at its end, then the short
    # scan that carries the states from chunk to chunk.
    to_end = jnp.exp(cs[..., -1:] - cs)  # [B, nc, H, Q]
    own = jnp.einsum("bchsp,bcsn->bchpn", (xd * to_end[..., None]).astype(dtype), b, preferred_element_type=f32)
    whole = jnp.exp(cs[..., -1])  # [B, nc, H]: a chunk's decay from end to end

    def carry_on(state, per_chunk):
        decay_c, own_c = per_chunk
        return decay_c[..., None, None] * state + own_c, state  # emits the state *entering* the chunk

    _, entering = jax.lax.scan(carry_on, jnp.zeros((rows, h, p, n), f32),
                               (whole.transpose(1, 0, 2), own.transpose(1, 0, 2, 3, 4)))
    entering = entering.transpose(1, 0, 2, 3, 4)  # [B, nc, H, P, N]
    carried = jnp.einsum("bctn,bchpn->bchtp", c, entering.astype(dtype), preferred_element_type=f32)
    y = y + jnp.exp(cs)[..., None] * carried
    return y.transpose(0, 1, 3, 2, 4).reshape(rows, nc * chunk, h, p)[:, :t]
