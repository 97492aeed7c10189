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
shared expert); an untied head; no multipliers. DeepSeek-V3's block
(``JoyAI-LLM-Flash``): multi-head latent attention (``"mla"``) and then a
feed-forward of the layer's own kind (``ffn_types``: a dense gated MLP or
routed SwiGLU experts), and one multi-token-prediction module beside the
head. Built from a :class:`HybridConfig`, which holds values and knows no
family. ``models/transformer_lm.py`` (the GPT-2 block) is a file of
its own and shares nothing with this one but the attention kernels and the
fused head.

Per token, ``n(x) = x / sqrt(mean(x²) + eps) * w``::

    h⁰ = embedding_multiplier · E[id]
    h ← h + residual_multiplier · mixer_l(n₁(h))
    h ← h + residual_multiplier · ffn_l(n₂(h))        for every layer l
    logits = n_f(h) Eᵀ / logits_scaling

    ffn_l:      ``ffn_types[l]`` where given: "dense", the gated MLP, or "moe",
                routed experts; else the gated MLP after every mixer where
                ``shared_intermediate_size`` is set, and nothing where not
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
                (experts relu² or, ``moe_activation="swiglu"``, gated)
    mla:        c_q = n_q(x W_qa);  [q_nope | q_rope] = c_q W_qb   (a head: nope | rope wide)
                [c_kv | k_r] = x W_kva;  c_kv ← n_kv(c_kv);  [k_nope | v] = c_kv W_kvb
                q = [q_nope | R_p q_rope];  k = [k_nope | R_p k_r]  (k_r one for all heads)
                out = causal softmax(q kᵀ / sqrt(nope + rope)) v W_o   (v: v_head_dim wide)
                R_p: ops/rotary.py's interleaved rotation at position p, rope_theta
    mtp:        h'_i = W_eh [n_h(h_i) ; n_e(E[t_{i+1}])]  (h: the last block's output,
                t_{i+1}: the batch's label at i);  h'' = block(h') (the last layer's kinds);
                its logits n_f'(h'') Eᵀ predict t_{i+2}, a second loss beside the
                first, weighted ``mtp_loss_weight`` (``make_fused_lm_loss``)

Attention resolves through ``ops/dispatch.py`` like every model's (flash on a
TPU; the kernels' scale is ``head_dim**-0.5``, so the configuration's is
folded into ``q``), and so do the scan (``ops/ssd.py``: its Pallas kernels on
a TPU where the shape tiles, its ``jax.numpy`` chunked form elsewhere) and the
causal convolution with its SiLU (``ops/causal_conv.py``'s kernels on a TPU,
``ops/ssd.py:causal_conv1d`` elsewhere), under the same ``pallas`` knob.
Parameters and norms' statistics are float32; ``dtype`` is what the matmuls
compute in.

``remat=True`` rematerialises every block (``nn.remat``): the backward pass
keeps one hidden state a block and computes each block's forward pass again
when it reaches it, which is what lets a stack whose training state fills
most of a chip train on thousands of tokens a step (docs/memory.md). One
thing crosses the recomputation besides the hidden state (``REMAT_POLICY``):
where attention runs the flash kernels, their output ``o`` and log-sum-exp
``lse``, ``B·T·H·(Dv·itemsize + 4)`` bytes an attention layer (at ``[2, 8192]``
in bfloat16 0.76 GiB over the benchmark's five latent layers of
``joyai-llm-flash`` and its prediction module's, 0.13 GiB for
``nemotron-3-nano``'s one attention layer; 0.03 GiB for
``granite-4.0-h-micro``'s at ``[2, 4096]``), so the forward kernel runs once a
step and not twice. Where attention takes the plain path nothing carries
those names and every block is recomputed whole. The model records which
(``kernel_dispatch`` op ``remat``, with the bytes kept).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

MAMBA, ATTENTION, MOE, MLA = "mamba", "attention", "moe", "mla"
DENSE = "dense"  # a feed-forward kind (``ffn_types``) beside MOE


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """What the stack is built from, as values. ``from_dict`` fills it from a
    published ``granitemoehybrid`` ``config.json``, whose keys these names
    are; whoever loads another family's file maps its keys onto them
    (``nemotron_h``'s: ``benchmarks/systems/nemotron_h.py:hybrid_config``;
    ``joyai_llm_flash``'s: ``benchmarks/systems/joyai_llm_flash.py``).
    The module's equations say what each field sizes; those of the latent
    layer, the feed-forward a layer and the prediction module, in brief::

        mla:   q = [q_nope | R_p q_rope] from n_q(x W_qa) W_qb   (q_lora_rank; qk_nope_head_dim | qk_rope_head_dim)
               k = [k_nope | R_p k_r], v from n_kv(c_kv) W_kvb   (kv_lora_rank; v_head_dim), rope_theta
        ffn_l: ffn_types[l], DENSE (shared_intermediate_size) or MOE (moe_intermediate_size, moe_activation)
        mtp:   mtp_layers modules, h'_i = W_eh [n_h(h_i) ; n_e(E[t_{i+1}])] → one block → the head,
               their loss weighted mtp_loss_weight"""

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
    moe_activation: str = "relu2"  # the experts' f: "relu2" or "swiglu"
    # "mla" layers: latent attention's ranks and head widths, and the rotary's base
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    # the feed-forward after each layer's mixer, one of DENSE / MOE a layer; () as above
    ffn_types: tuple = ()
    mtp_layers: int = 0  # multi-token-prediction modules (0 or 1)
    mtp_loss_weight: float = 0.0
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
        """Layers with routed experts, the multi-token-prediction module's block (the last layer's kinds) among them."""
        last = len(self.layer_types) - 1
        return sum(kind == MOE for kind in self.layer_types + self.ffn_types) + self.mtp_layers * (self.ffn(last) == MOE)

    def ffn(self, layer: int):
        """The feed-forward kind after layer ``layer``'s mixer: DENSE, MOE or None."""
        if self.ffn_types:
            return self.ffn_types[layer]
        return DENSE if self.shared_intermediate_size else None


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
    pallas: Any = None  # ops/dispatch.py's knob: True the scan's and the conv's kernels, False their jnp forms, None auto

    @nn.compact
    def __call__(self, u):
        from distributed_training_pytorch_tpu.ops import dispatch

        cfg = self.cfg
        heads, p, n, d_inner = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_inner
        groups = cfg.mamba_n_groups
        conv_dim = d_inner + 2 * groups * n
        rows, t, _ = u.shape
        with jax.named_scope("mamba_mixer"):
            proj = _dense(d_inner + conv_dim + heads, self.dtype, "in_proj")(u)
            z, dt = proj[..., :d_inner], proj[..., d_inner + conv_dim:]
            conv_w = self.param("conv_kernel", _conv_init, (cfg.mamba_d_conv, conv_dim), jnp.float32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
            with jax.named_scope("mamba_conv"):  # xBC read where in_proj left it: the conv takes the whole projection
                xbc = dispatch.conv_fn(cfg.dispatch_name, self.pallas)(proj, conv_w, conv_b, start=d_inner, dtype=self.dtype)
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


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434): queries
    through a low-rank bottleneck, keys and values from one compressed latent
    and one rotary key shared by every head; the attention itself is the flash
    kernels' at query/key width ``nope + rope`` and value width ``v_head_dim``
    (``ops/pallas.py:flash_attention``: scale ``(nope + rope)**-0.5``, as
    published without ``rope_scaling``). Scopes: ``mla_attention`` ›
    ``mla_q``, ``mla_kv``, ``mla_rope``."""

    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None  # ops/dispatch.py's knob: True flash, False plain, None auto

    @nn.compact
    def __call__(self, x):
        from distributed_training_pytorch_tpu.ops import dispatch, rotary

        cfg = self.cfg
        heads, nope, rope, vd = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        rows, t, _ = x.shape
        with jax.named_scope("mla_attention"):
            with jax.named_scope("mla_q"):
                c_q = RMSNorm(cfg.rms_norm_eps, self.dtype, name="q_a_layernorm")(_dense(cfg.q_lora_rank, self.dtype, "q_a_proj")(x))
                q = _dense(heads * (nope + rope), self.dtype, "q_b_proj")(c_q).reshape(rows, t, heads, nope + rope)
            with jax.named_scope("mla_kv"):
                c_kv, k_rope = jnp.split(_dense(cfg.kv_lora_rank + rope, self.dtype, "kv_a_proj_with_mqa")(x), [cfg.kv_lora_rank], axis=-1)
                c_kv = RMSNorm(cfg.rms_norm_eps, self.dtype, name="kv_a_layernorm")(c_kv)
                kv = _dense(heads * (nope + vd), self.dtype, "kv_b_proj")(c_kv).reshape(rows, t, heads, nope + vd)
                k_nope, v = jnp.split(kv, [nope], axis=-1)
            with jax.named_scope("mla_rope"):
                positions = jnp.arange(t)
                q = jnp.concatenate([q[..., :nope], rotary.rotate(q[..., nope:], positions, cfg.rope_theta)], axis=-1)
                k_rope = jnp.broadcast_to(rotary.rotate(k_rope, positions, cfg.rope_theta)[:, :, None, :], (rows, t, heads, rope))
                k = jnp.concatenate([k_nope, k_rope], axis=-1)
            attend = dispatch.attention_fn(cfg.dispatch_name, self.pallas, causal=True)
            if attend is None:
                from distributed_training_pytorch_tpu.ops.pallas import _causal_plain as attend
            y = attend(q, k, v).reshape(rows, t, heads * vd)
            return _dense(cfg.hidden_size, self.dtype, "o_proj")(y)


# What rematerialising a block keeps from the first forward pass: the flash
# kernel's residuals, under the names ops/pallas.py:_flash_fwd gives them
# (FLASH_OUT, FLASH_LSE; repeated here so that building a model does not
# import Pallas). Everything else a block computes is computed again.
FLASH_RESIDUALS = ("flash_out", "flash_lse")
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str
    dtype: Any = jnp.float32
    pallas: Any = None
    ffn: Any = None  # the feed-forward after the mixer: DENSE, MOE or None (HybridConfig.ffn)

    def _experts(self, name: str):
        from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

        cfg = self.cfg
        first, count = cfg.experts_held
        return HeldExpertsMlp(cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size, cfg.n_routed_experts,
                              first, count, cfg.num_experts_per_tok, cfg.routed_scaling_factor, self.dtype,
                              cfg.dispatch_name, cfg.moe_activation, name=name)

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg

        def add(h, y):  # in float32: the multiplier (0.22) is no bfloat16 number
            return (h.astype(jnp.float32) + cfg.residual_multiplier * y.astype(jnp.float32)).astype(self.dtype)

        if self.kind == MOE:
            # the router reads the norm's float32 output; the experts round it themselves
            h = add(h, self._experts("moe")(RMSNorm(cfg.rms_norm_eps, jnp.float32, name="input_layernorm")(h)))
        else:
            if self.kind == MAMBA:
                mixer = MambaMixer(cfg, self.dtype, self.pallas, name="mamba")
            elif self.kind == ATTENTION:
                mixer = GroupedQueryAttention(cfg, self.dtype, self.pallas, name="self_attn")
            elif self.kind == MLA:
                mixer = LatentAttention(cfg, self.dtype, self.pallas, name="self_attn")
            else:
                raise ValueError(f"unknown layer type {self.kind!r} (want {MAMBA!r}, {ATTENTION!r}, {MLA!r} or {MOE!r})")
            h = add(h, mixer(RMSNorm(cfg.rms_norm_eps, self.dtype, name="input_layernorm")(h)))
        if self.ffn is None:  # a layer is its one mixer
            return h
        if self.ffn == MOE:
            return add(h, self._experts("mlp")(RMSNorm(cfg.rms_norm_eps, jnp.float32, name="post_attention_layernorm")(h)))
        if self.ffn != DENSE:
            raise ValueError(f"unknown feed-forward kind {self.ffn!r} (want {DENSE!r} or {MOE!r})")
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="post_attention_layernorm")(h)
        with jax.named_scope("gated_mlp"):
            a, b = jnp.split(_dense(2 * cfg.shared_intermediate_size, self.dtype, "mlp_in")(x), 2, axis=-1)
            y = _dense(cfg.hidden_size, self.dtype, "mlp_out")(nn.silu(a) * b)
        return add(h, y)


class MultiTokenPrediction(nn.Module):
    """DeepSeek-V3's prediction module (arXiv:2412.19437 section 2.2), one
    step ahead: ``h`` the last block's output, ``after`` the embedding of each
    position's next token; the two normed and joined (hidden first, as the
    report writes it), projected back to the hidden size, one more block of
    the last layer's kinds (rematerialised where the stack's are) and a norm
    of its own. The head is the model's. Scope: ``mtp``."""

    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None
    remat: bool = True

    @nn.compact
    def __call__(self, h, after):
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        with jax.named_scope("mtp"):
            joined = jnp.concatenate([RMSNorm(eps, self.dtype, name="hnorm")(h), RMSNorm(eps, self.dtype, name="enorm")(after)], axis=-1)
            x = _dense(cfg.hidden_size, self.dtype, "eh_proj")(joined)
            block = nn.remat(HybridBlock, policy=REMAT_POLICY) if self.remat else HybridBlock
            last = len(cfg.layer_types) - 1
            x = block(cfg, cfg.layer_types[last], self.dtype, self.pallas, cfg.ffn(last), name="block")(x)
            return RMSNorm(eps, self.dtype, name="final_norm")(x)


class HybridLM(nn.Module):
    """Token ids ``[B, T]`` in, next-token logits out. ``return_hidden=True``
    hands back the final norm's output already divided by ``logits_scaling``,
    for ``ops.losses.tied_cross_entropy_loss`` with :meth:`head_matrix`
    (``models.transformer_lm.make_fused_lm_loss`` does exactly that); with a
    prediction module (``mtp_layers``) and ``labels`` (the batch's next tokens)
    given, the pair (main, module's), each to go through the same head."""

    cfg: HybridConfig
    dtype: Any = jnp.float32
    pallas: Any = None
    remat: bool = True

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, return_hidden: bool = False, labels=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_normal, name="embed")
        head = embed.embedding if cfg.tie_word_embeddings else self.param(
            "lm_head", _normal, (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        h = (cfg.embedding_multiplier * embed(tokens)).astype(self.dtype)
        block = nn.remat(HybridBlock, policy=REMAT_POLICY) if self.remat else HybridBlock
        for i, kind in enumerate(cfg.layer_types):
            h = block(cfg, kind, self.dtype, self.pallas, cfg.ffn(i), name=f"layer_{i}")(h)
        out = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(h)
        # the prediction module runs where its targets are given (and when the parameters are made)
        mtp = None
        if cfg.mtp_layers and (labels is not None or self.is_initializing()):
            after = (cfg.embedding_multiplier * embed(tokens if labels is None else labels)).astype(self.dtype)
            mtp = MultiTokenPrediction(cfg, self.dtype, self.pallas, self.remat, name="mtp")(h, after)
        if self.remat and not self.is_initializing():
            self._record_remat(tokens.shape, with_mtp=mtp is not None)
        if return_hidden:
            scaled = lambda x: x / jnp.asarray(cfg.logits_scaling, x.dtype)  # noqa: E731
            return (scaled(out), scaled(mtp)) if labels is not None and mtp is not None else scaled(out)
        return out.astype(jnp.float32) @ head.T.astype(jnp.float32) / cfg.logits_scaling

    def _record_remat(self, shape, with_mtp: bool):
        """Record what ``REMAT_POLICY`` keeps in this stack: one ``o`` and
        ``lse`` an attention layer (the prediction module's block too, where it
        runs) if attention resolves to flash, else nothing."""
        from distributed_training_pytorch_tpu.ops import dispatch

        cfg = self.cfg
        kinds = cfg.layer_types + (cfg.layer_types[-1:] if with_mtp else ())
        widths = [cfg.v_head_dim if kind == MLA else cfg.head_dim for kind in kinds if kind in (ATTENTION, MLA)]
        dispatch.remat_kept(cfg.dispatch_name, self.pallas, rows=shape[0], seq_len=shape[1],
                            heads=cfg.num_attention_heads, v_widths=widths, itemsize=jnp.dtype(self.dtype).itemsize)

    # -- what make_fused_lm_loss asks of a model --------------------------------

    def head_matrix(self, params):
        """The ``[V, d]`` matrix of the output head: the embedding where tied."""
        return params["embed"]["embedding"] if self.cfg.tie_word_embeddings else params["lm_head"]

    @property
    def sows_step_metrics(self) -> bool:
        return self.cfg.moe_layers > 0

    @property
    def mtp_loss_weight(self) -> float:
        """The prediction module's loss's weight in the objective (0: no module)."""
        return self.cfg.mtp_loss_weight if self.cfg.mtp_layers else 0.0

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


def JoyAITiny(vocab_size: int = 256, dtype: Any = jnp.float32, **kw) -> HybridLM:
    """Small variant of the DeepSeek-V3 block (``JoyAI-LLM-Flash``) for tests
    and ``LM_SIZE=joyai_tiny``: three latent-attention layers, the first with
    a dense gated MLP and the two after it with routed SwiGLU experts (eight
    published, the first four held here, top-3, a shared expert), query/key
    heads 24 wide (16 + 8 rotary) and value heads 16, one prediction module,
    an untied head."""
    cfg = HybridConfig(
        vocab_size=vocab_size, hidden_size=64, layer_types=(MLA, MLA, MLA), num_attention_heads=4,
        num_key_value_heads=4, mamba_n_heads=0, mamba_d_head=0, mamba_d_state=0, shared_intermediate_size=128,
        rms_norm_eps=1e-6, tie_word_embeddings=False, moe_intermediate_size=32, moe_shared_intermediate_size=32,
        n_routed_experts=8, experts_held=(0, 4), num_experts_per_tok=3, routed_scaling_factor=2.5,
        moe_activation="swiglu", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, ffn_types=(DENSE, MOE, MOE), mtp_layers=1, mtp_loss_weight=0.3,
        dispatch_name="joyai_llm_flash",
    )
    return HybridLM(cfg, dtype=dtype, **kw)
