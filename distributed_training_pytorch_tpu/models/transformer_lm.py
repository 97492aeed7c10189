"""Decoder-only transformer LM — the long-context showcase model family.

Beyond the reference's scope (its only model is VGG16, ``model/vgg16.py``);
this family exists so the framework's long-context and distributed machinery
has a first-class consumer, wired end-to-end:

* causal attention via the Pallas flash kernel (``ops.pallas``, auto on TPU
  for long sequences), ring attention (``parallel.ring_attention``) when the
  sequence is sharded over a ``seq`` mesh axis, or plain XLA attention;
* homogeneous pre-LN blocks — exactly the stacked-stage shape
  ``parallel.pipeline.pipeline_apply`` consumes for pipeline parallelism;
* optional Mixture-of-Experts FFNs (``parallel.moe.MoEMlp``) every
  ``moe_every``-th block for expert parallelism;
* bf16 activation knob with float32 params/logits, like the vision zoo.

Attention selection (``attention_impl``): ``"auto"`` = shape-aware flash on
TPU / plain elsewhere; ``"flash"`` = force the kernel; ``"plain"`` = XLA
softmax attention; ``"ring"`` = exact ring attention over the ``seq`` axis of
the ambient mesh (pass ``mesh=``).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_training_pytorch_tpu.parallel.moe import MoEMlp


def _causal_attention_fn(attention_impl: str, mesh):
    """Resolve ``attention_impl`` to a (q, k, v) -> out callable at apply time
    (lazily, so constructing a model never initializes jax backends). Flash vs
    plain goes through the ``ops/dispatch.py`` policy layer, which records the
    resolution — including the silent below-``FLASH_MIN_SEQ_LEN``
    fall-through — as a one-time ``kernel_dispatch`` decision."""
    from distributed_training_pytorch_tpu.ops import dispatch

    if attention_impl == "ring":
        if mesh is None:
            raise ValueError('attention_impl="ring" needs mesh=')
        from distributed_training_pytorch_tpu.parallel.ring_attention import ring_attention

        dispatch.record("transformer_lm", "attention", "ring", reason="attention_impl=ring")
        return lambda q, k, v: ring_attention(q, k, v, mesh, causal=True)
    if attention_impl in ("auto", "flash", "plain"):
        use_flash = {"auto": None, "flash": True, "plain": False}[attention_impl]
        fn = dispatch.attention_fn("transformer_lm", use_flash, causal=True)
        if fn is not None:
            return fn
        from distributed_training_pytorch_tpu.ops.pallas import _causal_plain

        return _causal_plain
    raise ValueError(f"unknown attention_impl {attention_impl!r}")


class DecoderBlock(nn.Module):
    """Pre-LN decoder block: x + attn(ln(x)); x + ffn(ln(x)).

    ``decode=True`` runs single-token autoregressive mode: ``x`` is
    ``[B, 1, d]``, and the block keeps a KV cache (``'cache'`` collection,
    ``[B, max_len, H, Dh]`` per projection) updated in place with one
    ``dynamic_update_slice`` per step — the standard TPU decode layout (static
    shapes; the growing sequence is a write index, not a growing tensor).
    ``max_len`` bounds the cache and is required for decode.
    """

    num_heads: int
    mlp_dim: int
    dropout_rate: float = 0.0
    dtype: Any = jnp.float32
    attention_impl: str = "auto"
    mesh: Any = None
    use_moe: bool = False
    num_experts: int = 8
    moe_num_groups: int = 1
    moe_capacity_factor: float = 1.25
    moe_dispatch_impl: str = "einsum"
    max_len: int = 2048

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        train: bool = False,
        decode: bool = False,
        decode_index: jax.Array | None = None,
    ) -> jax.Array:
        dim = x.shape[-1]
        if dim % self.num_heads:
            raise ValueError(f"hidden dim {dim} not divisible by {self.num_heads} heads")
        head_dim = dim // self.num_heads

        y = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.DenseGeneral(
            (3, self.num_heads, head_dim), axis=-1, dtype=self.dtype, name="qkv"
        )(y)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        if decode:
            if x.shape[1] != 1:
                raise ValueError(f"decode mode consumes one token at a time, got T={x.shape[1]}")
            if decode_index is None:
                raise ValueError("decode=True requires decode_index (the model's step counter)")
            b = x.shape[0]
            cached_k = self.variable(
                "cache",
                "cached_key",
                lambda: jnp.zeros((b, self.max_len, self.num_heads, head_dim), self.dtype),
            )
            cached_v = self.variable(
                "cache",
                "cached_value",
                lambda: jnp.zeros((b, self.max_len, self.num_heads, head_dim), self.dtype),
            )
            # One step counter lives on the model (the 'position' cache var);
            # per-block copies would be redundant state with a desync hazard.
            i = decode_index
            cached_k.value = jax.lax.dynamic_update_slice_in_dim(cached_k.value, k, i, 1)
            cached_v.value = jax.lax.dynamic_update_slice_in_dim(cached_v.value, v, i, 1)
            # q [B,1,H,Dh] against the cache prefix: mask positions > i.
            scale = head_dim**-0.5
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, cached_k.value).astype(jnp.float32)
            valid = jnp.arange(self.max_len) <= i
            logits = jnp.where(valid[None, None, None, :], logits * scale, -1e30)
            weights = jax.nn.softmax(logits, axis=-1).astype(self.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", weights, cached_v.value)
        else:
            attn_fn = _causal_attention_fn(self.attention_impl, self.mesh)
            y = attn_fn(q, k, v)
        y = nn.DenseGeneral(dim, axis=(-2, -1), dtype=self.dtype, name="attn_out")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        x = x + y

        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.use_moe:
            # Decode routes capacity-free (per-token expert gather — no
            # buffers, no drops), so KV-cache generation works for MoE LMs
            # with the same parameters the capacity-routed training saved.
            y = MoEMlp(
                num_experts=self.num_experts,
                hidden_dim=self.mlp_dim,
                num_groups=self.moe_num_groups,
                capacity_factor=self.moe_capacity_factor,
                dispatch_impl=self.moe_dispatch_impl,
                dtype=self.dtype,
                name="moe",
            )(y, decode=decode)
        else:
            y = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_in")(y)
            y = nn.gelu(y)
            y = nn.Dense(dim, dtype=self.dtype, name="mlp_out")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return x + y


class TransformerLM(nn.Module):
    """Token-in, next-token-logits-out causal LM.

    ``moe_every=k`` makes every k-th block (1-indexed) a MoE block; 0 = dense.
    """

    vocab_size: int
    hidden_dim: int = 512
    depth: int = 8
    num_heads: int = 8
    mlp_dim: int = 2048
    max_len: int = 2048
    dropout_rate: float = 0.0
    dtype: Any = jnp.float32
    attention_impl: str = "auto"
    # The unified kernel-policy knob (ops/dispatch.py): True -> "flash",
    # False -> "plain", None -> keep attention_impl (the historical program).
    pallas: Any = None
    mesh: Any = None
    moe_every: int = 0
    num_experts: int = 8
    moe_num_groups: int = 1
    moe_capacity_factor: float = 1.25
    moe_dispatch_impl: str = "einsum"
    tie_embeddings: bool = True

    def head_matrix(self, params):
        """The ``[V, d]`` matrix of the tied head (``make_fused_lm_loss`` asks it of a model)."""
        return params["embed"]["embedding"]

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        *,
        train: bool = False,
        decode: bool = False,
        return_hidden: bool = False,
    ) -> jax.Array:
        """``return_hidden=True`` skips the vocab projection and returns the
        final-LN hidden states ``[B, T, d]`` — pair with
        ``ops.losses.tied_cross_entropy_loss`` (and the ``embed`` param) so training
        never materializes the [B, T, V] float32 logits."""
        b, t = tokens.shape
        if t > self.max_len:
            raise ValueError(f"sequence {t} exceeds max_len {self.max_len}")
        embed = nn.Embed(
            self.vocab_size,
            self.hidden_dim,
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="embed",
        )
        x = embed(tokens).astype(self.dtype)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, self.max_len, self.hidden_dim),
            jnp.float32,
        )
        decode_index = None
        if decode:
            # single-token step: ONE position counter for the whole model
            position = self.variable("cache", "position", lambda: jnp.zeros((), jnp.int32))
            decode_index = position.value
            x = x + jax.lax.dynamic_slice_in_dim(pos, decode_index, 1, 1).astype(x.dtype)
            position.value = decode_index + 1
        else:
            x = x + jax.lax.dynamic_slice_in_dim(pos, 0, t, 1).astype(x.dtype)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        from distributed_training_pytorch_tpu.ops import dispatch

        attention_impl = dispatch.lm_attention_impl(self.attention_impl, self.pallas)
        for i in range(self.depth):
            x = DecoderBlock(
                self.num_heads,
                self.mlp_dim,
                self.dropout_rate,
                dtype=self.dtype,
                attention_impl=attention_impl,
                mesh=self.mesh,
                use_moe=self.moe_every > 0 and (i + 1) % self.moe_every == 0,
                num_experts=self.num_experts,
                moe_num_groups=self.moe_num_groups,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_dispatch_impl=self.moe_dispatch_impl,
                max_len=self.max_len,
            )(x, train=train, decode=decode, decode_index=decode_index)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if return_hidden:
            if not self.tie_embeddings:
                raise ValueError("return_hidden requires tie_embeddings=True")
            return x
        if self.tie_embeddings:
            logits = x.astype(jnp.float32) @ embed.embedding.T.astype(jnp.float32)
        else:
            logits = nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(
                x.astype(jnp.float32)
            )
        return logits


def make_fused_lm_loss(
    model: TransformerLM,
    *,
    aux_loss_coef: float = 0.01,
    z_loss_coef: float = 1e-3,
):
    """Engine LossFn for next-token training through the fused tied-embedding
    CE (``ops.losses.tied_cross_entropy_loss``): the head scans the sequence
    a slice at a time and takes its gradients in the forward pass, so the
    [B, T, V] float32 logits never materialize and no logit is computed
    twice. Batch contract: ``image`` = input tokens, ``label`` =
    next tokens, optional ``mask`` [B] pad weights. ONE implementation shared
    by the training entry and the benchmark so they measure the same
    computation.

    For MoE models (``moe_every > 0``) the routers' sown aux losses join the
    objective: Switch load-balance * ``aux_loss_coef`` + router-z *
    ``z_loss_coef`` (standard coefficients; without them routing collapses
    onto a few experts).

    What the function asks of ``model`` beyond ``apply(..., return_hidden=True)``:
    ``head_matrix(params)``, the ``[V, d]`` matrix the head multiplies by (the
    token embedding where tied; the head's mathematics does not care whose it
    is); optionally ``moe_every`` (the GPT-2 stack's routers, as above) and
    ``sows_step_metrics`` / ``step_metrics(intermediates)`` (a stack whose
    layers sow per-step counts, which join the step's metrics)."""
    from distributed_training_pytorch_tpu.ops.losses import tied_cross_entropy_loss

    has_moe = getattr(model, "moe_every", 0) > 0
    sows = bool(getattr(model, "sows_step_metrics", False))

    def loss_fn(params, model_state, batch, rng, train):
        kwargs = {"rngs": {"dropout": rng}} if train else {}
        if has_moe or sows:
            hidden, inter = model.apply(
                {"params": params},
                batch["image"],
                train=train,
                return_hidden=True,
                mutable=["intermediates"],
                **kwargs,
            )
        else:
            hidden = model.apply(
                {"params": params}, batch["image"], train=train, return_hidden=True, **kwargs
            )
        loss = tied_cross_entropy_loss(
            hidden, model.head_matrix(params), batch["label"], batch.get("mask")
        )
        metrics = {"loss": loss, "nll": loss, "ppl": jnp.exp(loss)}
        if sows:
            metrics.update(model.step_metrics(inter["intermediates"]))
        if has_moe:
            # mean of each sown metric across the MoE blocks, selected by name
            def collect(name):
                vals = [
                    v
                    for path, v in jax.tree_util.tree_flatten_with_path(
                        inter["intermediates"]
                    )[0]
                    if name in jax.tree_util.keystr(path)
                ]
                return jnp.mean(jnp.stack([jnp.asarray(v) for v in vals])) if vals else 0.0

            lb = collect("load_balance_loss")
            zl = collect("router_z_loss")
            loss = loss + aux_loss_coef * lb + z_loss_coef * zl
            metrics["moe_load_balance"] = lb
            metrics["moe_router_z"] = zl
            metrics["loss"] = loss
        return loss, (metrics, model_state)

    return loss_fn


def generate(
    model: TransformerLM,
    variables,
    prompt: jax.Array,
    num_steps: int,
    rng: jax.Array,
    *,
    temperature: float = 0.0,
) -> jax.Array:
    """Autoregressive sampling with the KV-cache decode path.

    ``prompt`` is ``[B, P]`` int32; returns ``[B, P + num_steps]``. One
    ``lax.scan`` covers prefill and generation — every step is a single-token
    cached decode (static shapes throughout). The whole decode is jitted
    (model/num_steps/temperature static), so a repeat call with the same
    shapes is ONE device dispatch — unjitted, ``lax.scan`` re-traces the
    decoder body on every call, which costs seconds of host time per sample
    and dominates through a remote-dispatch link.
    ``temperature=0`` is greedy; otherwise softmax sampling at that
    temperature.
    """
    total = prompt.shape[1] + num_steps
    if total > model.max_len:
        raise ValueError(
            f"prompt {prompt.shape[1]} + steps {num_steps} exceeds max_len {model.max_len}"
        )
    return _generate_jit(model, variables, prompt, num_steps, rng, temperature)


@functools.partial(jax.jit, static_argnums=(0, 3, 5))
def _generate_jit(model, variables, prompt, num_steps, rng, temperature):
    b, p = prompt.shape
    total = p + num_steps
    params = {k: v for k, v in variables.items() if k != "cache"}

    # The cache initializes to zeros (its variable defaults), so its structure
    # from eval_shape IS its initial value.
    cache_shapes = jax.eval_shape(
        lambda: model.apply(params, prompt[:, :1], decode=True, mutable=["cache"])
    )[1]["cache"]
    cache0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes)

    def step(carry, t):
        token, cache, rng = carry
        logits, updated = model.apply(
            {**params, "cache": cache}, token, decode=True, mutable=["cache"]
        )
        logits = logits[:, 0, :]  # [B, V]
        rng, sample_rng = jax.random.split(rng)
        if temperature > 0.0:
            sampled = jax.random.categorical(sample_rng, logits / temperature, axis=-1)
        else:
            sampled = jnp.argmax(logits, axis=-1)
        # While still inside the prompt, feed the ground-truth next token.
        next_idx = jnp.minimum(t + 1, p - 1)
        in_prompt = (t + 1) < p
        next_token = jnp.where(
            in_prompt, jax.lax.dynamic_index_in_dim(prompt, next_idx, 1), sampled[:, None]
        )
        return (next_token, updated["cache"], rng), next_token[:, 0]

    (_, _, _), produced = jax.lax.scan(
        step, (prompt[:, :1], cache0, rng), jnp.arange(total - 1)
    )
    # produced[t] is the token at position t+1.
    return jnp.concatenate([prompt[:, :1], produced.T], axis=1)


def GPTSmall(vocab_size: int = 50257, dtype: Any = jnp.float32, **kw) -> TransformerLM:
    """GPT-2-small-shaped config (117M dense params)."""
    kw.setdefault("max_len", 1024)
    return TransformerLM(
        vocab_size=vocab_size,
        hidden_dim=768,
        depth=12,
        num_heads=12,
        mlp_dim=3072,
        dtype=dtype,
        **kw,
    )


def LMTiny(vocab_size: int = 256, dtype: Any = jnp.float32, **kw) -> TransformerLM:
    """Small variant for tests."""
    kw.setdefault("max_len", 128)
    return TransformerLM(
        vocab_size=vocab_size,
        hidden_dim=32,
        depth=2,
        num_heads=4,
        mlp_dim=64,
        dtype=dtype,
        **kw,
    )
