"""Hybrid state-space / attention decoder LM: the second decoder block.

The stack of IBM's ``granitemoehybrid`` models without routed experts
(``ibm-granite/granite-4.0-h-*``): pre-RMSNorm blocks whose sequence mixer is,
by the layer's kind, a Mamba-2 mixer (``"mamba"``) or grouped-query causal
attention without positions (``"attention"``), each followed by one shared
gated (SwiGLU) MLP; a tied head; and Granite's four multipliers. Built from a
:class:`HybridConfig`, which reads the keys of the model's published
``config.json``. ``models/transformer_lm.py`` (the GPT-2 block) is a file of
its own and shares nothing with this one but the attention kernels and the
fused head.

Per token, ``n(x) = x / sqrt(mean(x²) + eps) * w``::

    h⁰ = embedding_multiplier · E[id]
    h ← h + residual_multiplier · mixer_l(n₁(h))
    h ← h + residual_multiplier · mlp(n₂(h))          for every layer l
    logits = n_f(h) Eᵀ / logits_scaling

    mlp:        [a | b] = x W_in;  y = (silu(a) ⊙ b) W_out
    attention:  causal softmax(attention_multiplier · q kᵀ) v; each key/value
                head serves ``heads / kv_heads`` consecutive query heads
    mamba:      [z | xBC | dt] = u W_in;  xBC = silu(conv(xBC)) (causal,
                depthwise, ``d_conv`` taps, bias);  [x | B | C] = xBC;
                Δ = softplus(dt + dt_bias);  A = −exp(A_log);
                S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ;  y_t = S_t C_t + D x_t;
                out = n_g(y ⊙ silu(z)) W_out

Attention resolves through ``ops/dispatch.py`` like every model's (flash on a
TPU; the kernels' scale is ``head_dim**-0.5``, so the configuration's is
folded into ``q``); the scan is ``ops/ssd.py``'s chunked form. Parameters and
norms' statistics are float32; ``dtype`` is what the matmuls compute in.

``remat=True`` rematerialises every block (``nn.remat``): the backward pass
keeps one hidden state a block and computes each block's forward pass again
when it reaches it, which is what lets a stack whose training state fills
most of a chip train on thousands of tokens a step (docs/memory.md).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_training_pytorch_tpu.ops.ssd import causal_conv1d, ssd_chunked

MAMBA, ATTENTION = "mamba", "attention"
REMAT_COUNTER = "hybrid_lm.blocks_rematerialised"  # profiling.trace.count: a block built under nn.remat


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The published ``config.json`` keys this stack reads, under their names."""

    vocab_size: int
    hidden_size: int
    layer_types: tuple
    num_attention_heads: int
    num_key_value_heads: int
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None  # None: head_dim**-0.5
    logits_scaling: float = 1.0

    @classmethod
    def from_dict(cls, cfg: dict) -> "HybridConfig":
        """From a ``config.json``'s keys; what this stack cannot do is refused here."""
        wanted = {"mamba_n_groups": 1, "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
                  "position_embedding_type": "nope", "num_local_experts": 0, "tie_word_embeddings": True,
                  "hidden_act": "silu", "normalization_function": "rmsnorm"}
        for key, value in wanted.items():
            if cfg.get(key, value) != value:
                raise NotImplementedError(f"HybridLM: {key}={cfg[key]!r} is not supported (only {value!r})")
        if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg.get("mamba_expand", 2) * cfg["hidden_size"]:
            raise ValueError("mamba_n_heads * mamba_d_head must equal mamba_expand * hidden_size")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: tuple(v) if k == "layer_types" else v for k, v in cfg.items() if k in names})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head


_normal = nn.initializers.normal(stddev=0.02)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=_normal, name=name)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x²) + eps) * scale`` in float32, handed on as ``dtype``."""

    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale).astype(self.dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    """U(±1/sqrt(taps)): the fan-in rule Mamba-2 leaves its depthwise ``nn.Conv1d`` at."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a log-uniform Δ on [1e-3, 1e-1] (Mamba-2's own)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    cfg: HybridConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        heads, p, n, d_inner = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_inner
        conv_dim = d_inner + 2 * n
        rows, t, _ = u.shape
        with jax.named_scope("mamba_mixer"):
            z, xbc, dt = jnp.split(_dense(d_inner + conv_dim + heads, self.dtype, "in_proj")(u),
                                   [d_inner, d_inner + conv_dim], axis=-1)
            conv_w = self.param("conv_kernel", _conv_init, (cfg.mamba_d_conv, conv_dim), jnp.float32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
            with jax.named_scope("mamba_conv"):
                xbc = nn.silu(causal_conv1d(xbc, conv_w, conv_b)).astype(self.dtype)
            x, b, c = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
            x = x.reshape(rows, t, heads, p)
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            with jax.named_scope("ssd_scan"):
                y = ssd_chunked(x, dt, -jnp.exp(a_log), b, c, chunk=cfg.mamba_chunk_size, dtype=self.dtype)
            y = (y + skip[:, None] * x.astype(jnp.float32)).reshape(rows, t, d_inner)
            gated = RMSNorm(cfg.rms_norm_eps, self.dtype, name="norm")(y * nn.silu(z.astype(jnp.float32)))
            return _dense(cfg.hidden_size, self.dtype, "out_proj")(gated)


class GroupedQueryAttention(nn.Module):
    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None  # ops/dispatch.py's knob: True flash, False plain, None auto

    @nn.compact
    def __call__(self, x):
        from distributed_training_pytorch_tpu.ops import dispatch

        cfg = self.cfg
        heads, kv_heads, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        rows, t, _ = x.shape
        with jax.named_scope("gqa_attention"):
            q = _dense(heads * hd, self.dtype, "q_proj")(x).reshape(rows, t, heads, hd)
            k = _dense(kv_heads * hd, self.dtype, "k_proj")(x).reshape(rows, t, kv_heads, hd)
            v = _dense(kv_heads * hd, self.dtype, "v_proj")(x).reshape(rows, t, kv_heads, hd)
            # Every attention path of ops/ takes [B, T, H, D] of equal head counts and
            # scales by D**-0.5: the groups are repeated outside (the repeat's gradient
            # sums over a group) and the configuration's scale is folded into q.
            k, v = (jnp.repeat(kv, heads // kv_heads, axis=2) for kv in (k, v))
            if cfg.attention_multiplier is not None:
                q = q * jnp.asarray(cfg.attention_multiplier * hd**0.5, q.dtype)
            attend = dispatch.attention_fn("hybrid_lm", self.pallas, causal=True)
            if attend is None:
                from distributed_training_pytorch_tpu.ops.pallas import _causal_plain as attend
            y = attend(q, k, v).reshape(rows, t, heads * hd)
            return _dense(cfg.hidden_size, self.dtype, "o_proj")(y)


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str
    dtype: Any = jnp.float32
    pallas: Any = None

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        if self.kind == MAMBA:
            mixer = MambaMixer(cfg, self.dtype, name="mamba")
        elif self.kind == ATTENTION:
            mixer = GroupedQueryAttention(cfg, self.dtype, self.pallas, name="self_attn")
        else:
            raise ValueError(f"unknown layer type {self.kind!r} (want {MAMBA!r} or {ATTENTION!r})")

        def add(h, y):  # in float32: the multiplier (0.22) is no bfloat16 number
            return (h.astype(jnp.float32) + cfg.residual_multiplier * y.astype(jnp.float32)).astype(self.dtype)

        h = add(h, mixer(RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_layernorm")(h)))
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="post_attention_layernorm")(h)
        with jax.named_scope("gated_mlp"):
            a, b = jnp.split(_dense(2 * cfg.shared_intermediate_size, self.dtype, "mlp_in")(x), 2, axis=-1)
            y = _dense(cfg.hidden_size, self.dtype, "mlp_out")(nn.silu(a) * b)
        return add(h, y)


class HybridLM(nn.Module):
    """Token ids ``[B, T]`` in, next-token logits out. ``return_hidden=True``
    hands back the final norm's output already divided by ``logits_scaling``,
    for ``ops.losses.tied_cross_entropy_loss`` with the ``embed`` parameter
    (``models.transformer_lm.make_fused_lm_loss`` does exactly that)."""

    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None
    remat: bool = True

    moe_every = 0  # what make_fused_lm_loss asks a model: no routed experts here

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, return_hidden: bool = False):
        from distributed_training_pytorch_tpu.ops import dispatch
        from distributed_training_pytorch_tpu.profiling.trace import count

        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_normal, name="embed")
        h = (cfg.embedding_multiplier * embed(tokens)).astype(self.dtype)
        if MAMBA in cfg.layer_types:
            dispatch.record("hybrid_lm", "ssd", "chunked",
                            reason=f"ops/ssd.py, jax.numpy dual form at chunk {cfg.mamba_chunk_size}: no scan kernel yet")
        block = nn.remat(HybridBlock) if self.remat else HybridBlock
        for i, kind in enumerate(cfg.layer_types):
            h = block(cfg, kind, self.dtype, self.pallas, name=f"layer_{i}")(h)
        if self.remat:
            count(REMAT_COUNTER, len(cfg.layer_types))
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(h)
        if return_hidden:
            return h / jnp.asarray(cfg.logits_scaling, h.dtype)
        return h.astype(jnp.float32) @ embed.embedding.T.astype(jnp.float32) / cfg.logits_scaling


TINY_LAYERS = (MAMBA, MAMBA, ATTENTION, MAMBA)


def HybridTiny(vocab_size: int = 256, dtype: Any = jnp.float32, **kw) -> HybridLM:
    """Small variant for tests and ``LM_SIZE=hybrid_tiny``: the four
    multipliers and the kinds' order of the Granite stack, toy widths."""
    cfg = HybridConfig(
        vocab_size=vocab_size, hidden_size=64, layer_types=TINY_LAYERS, num_attention_heads=4,
        num_key_value_heads=2, shared_intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, mamba_chunk_size=8, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 16, logits_scaling=8.0,
    )
    return HybridLM(cfg, dtype=dtype, **kw)
