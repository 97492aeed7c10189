"""ResNet in Flax — BASELINE.json config 3 (ResNet-50 / ImageNet-1k, DP).

The reference's only model is VGG16 (``model/vgg16.py``); ResNet extends the
zoo per the driver's scale-out configs (SURVEY.md §7 step 8). TPU-first
choices: NHWC layout, bfloat16 activation knob with float32 params and
float32 BatchNorm statistics, and *global* batch statistics for free — under
``jit`` with a data-sharded batch, BN's mean/var reductions span the global
batch (XLA inserts the cross-device collective), which DDP only approximates
with SyncBatchNorm.

BatchNorm running stats live in the ``batch_stats`` collection and flow
through ``TrainState.model_state`` (the engine threads mutable collections —
``train/engine.py`` ``make_supervised_loss``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

conv_kernel_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


class PallasConv1x1(nn.Module):
    """1x1 conv as a Pallas GEMM (``ops.pallas.conv1x1_bn_act_diff`` with an
    identity epilogue): swaps ResNet stage-1's bandwidth-bound
    56x56x(64<->256) 1x1 convs onto the hand-tiled GEMM. Kernel vs XLA conv
    is not measured on today's chip (scripts/resnet_pallas_probe.py). Kernel param
    keeps nn.Conv's ``[1, 1, Cin, Cout]`` layout; stride subsamples rows
    before the GEMM (a strided 1x1 conv reads only those pixels)."""

    features: int
    strides: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from distributed_training_pytorch_tpu.ops.pallas import conv1x1_bn_act_diff

        cin = x.shape[-1]
        kernel = self.param(
            "kernel", conv_kernel_init, (1, 1, cin, self.features), jnp.float32
        )
        if self.strides > 1:
            x = x[:, :: self.strides, :: self.strides, :]
        return conv1x1_bn_act_diff(
            x.astype(self.dtype),
            kernel.reshape(cin, self.features).astype(self.dtype),
            jnp.ones((self.features,), jnp.float32),
            jnp.zeros((self.features,), jnp.float32),
            relu=False,
            affine_grads=False,  # identity epilogue: constants, not params
        )


class BottleneckBlock(nn.Module):
    """1x1 reduce -> 3x3 -> 1x1 expand (x4), residual add, post-add ReLU."""

    features: int
    strides: int = 1
    dtype: Any = jnp.float32
    pallas_1x1: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        conv = partial(
            nn.Conv, use_bias=False, dtype=self.dtype, kernel_init=conv_kernel_init
        )

        def conv1x1(features, strides=1):
            def apply(inp):
                # Kernel only where the GEMM is bandwidth-bound (stage-1's
                # 56x56 maps, ~28 FLOP/byte); the deeper stages' 1x1s are
                # compute-bound and XLA's conv + fusion wins there.
                if self.pallas_1x1 and inp.shape[1] >= 56:
                    return PallasConv1x1(
                        features, strides=strides, dtype=self.dtype
                    )(inp)
                return conv(features, (1, 1), strides=(strides, strides))(inp)

            return apply
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            param_dtype=jnp.float32,
        )
        residual = x
        y = conv1x1(self.features)(x)
        y = nn.relu(norm()(y))
        y = conv(self.features, (3, 3), strides=(self.strides, self.strides))(y)
        y = nn.relu(norm()(y))
        y = conv1x1(self.features * 4)(y)
        # Zero-init the last BN scale: identity residual at init (He et al.).
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv1x1(self.features * 4, strides=self.strides)(residual)
            residual = norm()(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """Bottleneck ResNet; ``stage_sizes=(3, 4, 6, 3)`` is ResNet-50."""

    num_classes: int = 1000
    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    width: int = 64
    dtype: Any = jnp.float32
    # Route the bandwidth-bound stage-1 1x1 convs (input spatial >= 56, see
    # BottleneckBlock.conv1x1's gate) through the Pallas GEMM (PallasConv1x1).
    # Changes the param tree (module names), so flip only on fresh inits.
    # Not measured on today's chip (an earlier round found it slower inside
    # the step: a fusion barrier) — a measurement knob, not a perf default.
    pallas_1x1: bool = False
    # The unified kernel-policy knob (ops/dispatch.py): overrides pallas_1x1
    # when not None. Auto (None) resolves to OFF — promotion of the fused
    # 1x1 path stays evidence-gated (ROADMAP S2(c)).
    pallas: Optional[bool] = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        from distributed_training_pytorch_tpu.ops import dispatch

        pallas_1x1 = dispatch.conv1x1_policy(
            "resnet", self.pallas, legacy=self.pallas_1x1
        )
        x = x.astype(self.dtype)
        x = nn.Conv(
            self.width,
            (7, 7),
            strides=(2, 2),
            padding=[(3, 3), (3, 3)],
            use_bias=False,
            dtype=self.dtype,
            kernel_init=conv_kernel_init,
        )(x)
        x = nn.relu(
            nn.BatchNorm(
                use_running_average=not train,
                momentum=0.9,
                epsilon=1e-5,
                dtype=self.dtype,
                param_dtype=jnp.float32,
            )(x)
        )
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for stage, num_blocks in enumerate(self.stage_sizes):
            for block in range(num_blocks):
                x = BottleneckBlock(
                    self.width * (2**stage),
                    strides=2 if stage > 0 and block == 0 else 1,
                    dtype=self.dtype,
                    pallas_1x1=pallas_1x1,
                )(x, train=train)
        x = x.mean(axis=(1, 2))  # global average pool
        x = nn.Dense(
            self.num_classes,
            dtype=self.dtype,
            kernel_init=nn.initializers.normal(0.01),
        )(x)
        return x.astype(jnp.float32)


def ResNet50(
    num_classes: int = 1000,
    dtype: Any = jnp.float32,
    pallas_1x1: bool = False,
    pallas: Optional[bool] = None,
) -> ResNet:
    return ResNet(
        num_classes=num_classes, stage_sizes=(3, 4, 6, 3), dtype=dtype,
        pallas_1x1=pallas_1x1, pallas=pallas,
    )


def ResNet18Slim(num_classes: int = 10, dtype: Any = jnp.float32, **kw) -> ResNet:
    """Small bottleneck variant for tests/smoke runs (not torch ResNet-18)."""
    return ResNet(
        num_classes=num_classes, stage_sizes=(1, 1, 1, 1), width=16, dtype=dtype, **kw
    )
