"""Hybrid state-space / attention decoder LM: the second decoder block.

Two published stacks, one set of modules. IBM's ``granitemoehybrid`` without
routed experts (``ibm-granite/granite-4.0-h-*``): pre-RMSNorm blocks whose
sequence mixer is, by the layer's kind, a Mamba-2 mixer (``"mamba"``) or
grouped-query causal attention without positions (``"attention"``), each
followed by one shared gated (SwiGLU) MLP; a tied head; Granite's four
multipliers. NVIDIA's ``nemotron_h`` (``NVIDIA-Nemotron-3-Nano-30B-A3B``): a
layer is **one** mixer and no MLP, ``h ← h + mixer(n(h))``, the mixer a Mamba-2
mixer with grouped ``B`` / ``C`` and a grouped gated norm, grouped-query
attention with a stated head size, or (``"moe"``) routed experts
(``parallel/moe.py:HeldExpertsMlp``: sigmoid scores, a selection-only bias,
dropless top-k over the published count, the chip's share of the experts, a
shared expert); an untied head; no multipliers. Built from a
:class:`HybridConfig`, which holds values and knows no family. ``models/transformer_lm.py`` (the GPT-2 block) is a file of
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
                depthwise, ``d_conv`` taps, bias);  [x | B | C] = xBC (B, C in
                ``mamba_n_groups`` groups of ``d_state``; head h reads group
                h // (heads / groups));
                Δ = softplus(dt + dt_bias);  A = −exp(A_log);
                S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ;  y_t = S_t C_t + D x_t;
                out = n_g(y ⊙ silu(z)) W_out, n_g's statistic over each
                group's ``d_inner / groups`` channels
    moe:        parallel/moe.py:HeldExpertsMlp on the float32 norm output

Attention resolves through ``ops/dispatch.py`` like every model's (flash on a
TPU; the kernels' scale is ``head_dim**-0.5``, so the configuration's is
folded into ``q``), and so does the scan (``ops/ssd.py``: its Pallas kernels on
a TPU where the shape tiles, its ``jax.numpy`` chunked form elsewhere), under
the same ``pallas`` knob. Parameters and norms' statistics are float32;
``dtype`` is what the matmuls compute in.

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

from distributed_training_pytorch_tpu.ops.ssd import causal_conv1d

MAMBA, ATTENTION, MOE = "mamba", "attention", "moe"
REMAT_COUNTER = "hybrid_lm.blocks_rematerialised"  # profiling.trace.count: a block built under nn.remat


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """What the stack is built from, as values. ``from_dict`` fills it from a
    published ``granitemoehybrid`` ``config.json``, whose keys these names
    are; whoever loads another family's file maps its keys onto them
    (``nemotron_h``'s: ``benchmarks/systems/nemotron_h.py:hybrid_config``)."""

    vocab_size: int
    hidden_size: int
    layer_types: tuple
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    shared_intermediate_size: int = 0  # the gated MLP after every mixer; 0: a layer is its one mixer
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_head_dim: int | None = None  # None: hidden_size // num_attention_heads
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None  # None: head_dim**-0.5
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = True
    # "moe" layers: the published count routed over, and the experts held here
    moe_intermediate_size: int = 0
    moe_shared_intermediate_size: int = 0
    n_routed_experts: int = 0
    experts_held: tuple = (0, 0)  # (first, count)
    num_experts_per_tok: int = 0
    routed_scaling_factor: float = 1.0
    dispatch_name: str = "hybrid_lm"  # whose kernel_dispatch records the stack's are

    @classmethod
    def from_dict(cls, cfg: dict) -> "HybridConfig":
        """From a ``granitemoehybrid`` ``config.json``'s keys; what this stack cannot do is refused here."""
        wanted = {"mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
                  "position_embedding_type": "nope", "num_local_experts": 0, "tie_word_embeddings": True,
                  "hidden_act": "silu", "normalization_function": "rmsnorm"}
        for key, value in wanted.items():
            if cfg.get(key, value) != value:
                raise NotImplementedError(f"HybridLM: {key}={cfg[key]!r} is not supported (only {value!r})")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: tuple(v) if k == "layer_types" else v for k, v in cfg.items() if k in names})

    @property
    def head_dim(self) -> int:
        return self.attention_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def moe_layers(self) -> int:
        return sum(kind == MOE for kind in self.layer_types)


_normal = nn.initializers.normal(stddev=0.02)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=_normal, name=name)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x²) + eps) * scale`` in float32, handed on as ``dtype``;
    with ``groups`` the mean is over each of that many equal runs of channels."""

    eps: float
    dtype: Any = jnp.float32
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        if self.groups > 1:
            runs = x.reshape(x.shape[:-1] + (self.groups, -1))
            x = (runs * jax.lax.rsqrt(jnp.mean(jnp.square(runs), axis=-1, keepdims=True) + self.eps)).reshape(x.shape)
            return (x * scale).astype(self.dtype)
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
    pallas: Any = None  # ops/dispatch.py's knob: True the scan's kernels, False its chunked form, None auto

    @nn.compact
    def __call__(self, u):
        from distributed_training_pytorch_tpu.ops import dispatch

        cfg = self.cfg
        heads, p, n, d_inner = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_inner
        groups = cfg.mamba_n_groups
        conv_dim = d_inner + 2 * groups * n
        rows, t, _ = u.shape
        with jax.named_scope("mamba_mixer"):
            z, xbc, dt = jnp.split(_dense(d_inner + conv_dim + heads, self.dtype, "in_proj")(u),
                                   [d_inner, d_inner + conv_dim], axis=-1)
            conv_w = self.param("conv_kernel", _conv_init, (cfg.mamba_d_conv, conv_dim), jnp.float32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
            with jax.named_scope("mamba_conv"):
                xbc = nn.silu(causal_conv1d(xbc, conv_w, conv_b)).astype(self.dtype)
            x, b, c = jnp.split(xbc, [d_inner, d_inner + groups * n], axis=-1)
            x = x.reshape(rows, t, heads, p)
            if groups > 1:
                b, c = b.reshape(rows, t, groups, n), c.reshape(rows, t, groups, n)
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            with jax.named_scope("ssd_scan"):
                scan = dispatch.ssd_fn(cfg.dispatch_name, self.pallas)
                y = scan(x, dt, -jnp.exp(a_log), b, c, chunk=cfg.mamba_chunk_size, dtype=self.dtype)
            y = (y + skip[:, None] * x.astype(jnp.float32)).reshape(rows, t, d_inner)
            gated = RMSNorm(cfg.rms_norm_eps, self.dtype, groups, name="norm")(y * nn.silu(z.astype(jnp.float32)))
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
            attend = dispatch.attention_fn(cfg.dispatch_name, self.pallas, causal=True)
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

        def add(h, y):  # in float32: the multiplier (0.22) is no bfloat16 number
            return (h.astype(jnp.float32) + cfg.residual_multiplier * y.astype(jnp.float32)).astype(self.dtype)

        if self.kind == MOE:
            from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

            first, count = cfg.experts_held
            experts = HeldExpertsMlp(cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size, cfg.n_routed_experts,
                                     first, count, cfg.num_experts_per_tok, cfg.routed_scaling_factor, self.dtype,
                                     cfg.dispatch_name, name="moe")
            # the router reads the norm's float32 output; the experts round it themselves
            h = add(h, experts(RMSNorm(cfg.rms_norm_eps, jnp.float32, name="input_layernorm")(h)))
        else:
            if self.kind == MAMBA:
                mixer = MambaMixer(cfg, self.dtype, self.pallas, name="mamba")
            elif self.kind == ATTENTION:
                mixer = GroupedQueryAttention(cfg, self.dtype, self.pallas, name="self_attn")
            else:
                raise ValueError(f"unknown layer type {self.kind!r} (want {MAMBA!r}, {ATTENTION!r} or {MOE!r})")
            h = add(h, mixer(RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_layernorm")(h)))
        if not cfg.shared_intermediate_size:  # a layer is its one mixer
            return h
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="post_attention_layernorm")(h)
        with jax.named_scope("gated_mlp"):
            a, b = jnp.split(_dense(2 * cfg.shared_intermediate_size, self.dtype, "mlp_in")(x), 2, axis=-1)
            y = _dense(cfg.hidden_size, self.dtype, "mlp_out")(nn.silu(a) * b)
        return add(h, y)


class HybridLM(nn.Module):
    """Token ids ``[B, T]`` in, next-token logits out. ``return_hidden=True``
    hands back the final norm's output already divided by ``logits_scaling``,
    for ``ops.losses.tied_cross_entropy_loss`` with :meth:`head_matrix`
    (``models.transformer_lm.make_fused_lm_loss`` does exactly that)."""

    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None
    remat: bool = True

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, return_hidden: bool = False):
        from distributed_training_pytorch_tpu.profiling.trace import count

        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_normal, name="embed")
        head = embed.embedding if cfg.tie_word_embeddings else self.param(
            "lm_head", _normal, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        h = (cfg.embedding_multiplier * embed(tokens)).astype(self.dtype)
        block = nn.remat(HybridBlock) if self.remat else HybridBlock
        for i, kind in enumerate(cfg.layer_types):
            h = block(cfg, kind, self.dtype, self.pallas, name=f"layer_{i}")(h)
        if self.remat:
            count(REMAT_COUNTER, len(cfg.layer_types))
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(h)
        if return_hidden:
            return h / jnp.asarray(cfg.logits_scaling, h.dtype)
        return h.astype(jnp.float32) @ head.T.astype(jnp.float32) / cfg.logits_scaling

    # -- what make_fused_lm_loss asks of a model --------------------------------

    def head_matrix(self, params):
        """The ``[V, d]`` matrix of the output head: the embedding where tied."""
        return params["embed"]["embedding"] if self.cfg.tie_word_embeddings else params["lm_head"]

    @property
    def sows_step_metrics(self) -> bool:
        return self.cfg.moe_layers > 0

    def step_metrics(self, intermediates) -> dict:
        """The routing the step itself did, from what the expert layers sowed:
        pairs held here summed over the layers, the fullest expert's the maximum."""
        sown = {name: [jnp.asarray(v) for path, v in jax.tree_util.tree_flatten_with_path(intermediates)[0]
                       if name in jax.tree_util.keystr(path)] for name in ("moe_pairs_local", "moe_pairs_max_expert")}
        return {"moe_pairs_local": jnp.sum(jnp.stack(sown["moe_pairs_local"])),
                "moe_pairs_max_expert": jnp.max(jnp.stack(sown["moe_pairs_max_expert"]))}


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


def NemotronHTiny(vocab_size: int = 256, dtype: Any = jnp.float32, **kw) -> HybridLM:
    """Small variant of the ``nemotron_h`` stack for tests and ``LM_SIZE=nemotron_h_tiny``:
    one mixer a layer in the order ``MEM*E``, two ``B`` / ``C`` groups, eight routed
    experts of which the first four live here, top-3, an untied head."""
    cfg = HybridConfig(
        vocab_size=vocab_size, hidden_size=64, layer_types=(MAMBA, MOE, MAMBA, ATTENTION, MOE), num_attention_heads=4,
        num_key_value_heads=2, attention_head_dim=32, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_n_groups=2, mamba_chunk_size=8, tie_word_embeddings=False, moe_intermediate_size=48,
        moe_shared_intermediate_size=96, n_routed_experts=8, experts_held=(0, 4), num_experts_per_tok=3,
        routed_scaling_factor=2.5, dispatch_name="nemotron_h",
    )
    return HybridLM(cfg, dtype=dtype, **kw)
