from distributed_training_pytorch_tpu.models.vgg import VGG16, ConvBlock  # noqa: F401
from distributed_training_pytorch_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18Slim,
    ResNet50,
)
from distributed_training_pytorch_tpu.models.vit import ViT, ViTB16, ViTTiny  # noqa: F401
from distributed_training_pytorch_tpu.models.convnext import (  # noqa: F401
    ConvNeXt,
    ConvNeXtL,
    ConvNeXtTiny,
)
from distributed_training_pytorch_tpu.models.wrappers import InputNormalizer  # noqa: F401
from distributed_training_pytorch_tpu.models.transformer_lm import (  # noqa: F401
    DecoderBlock,
    GPTSmall,
    LMTiny,
    TransformerLM,
)
from distributed_training_pytorch_tpu.models.hybrid_lm import (  # noqa: F401
    HybridBlock,
    HybridConfig,
    HybridLM,
    HybridTiny,
    NemotronHTiny,
)


def create_model(name: str, num_classes: int, **kwargs):
    """Model-zoo factory. Names match BASELINE.json configs.

    Every model accepts the unified ``pallas=`` kernel-policy knob
    (ops/dispatch.py). VGG16 has no fused-kernel coverage (3x3 convs), so the
    knob is consumed here and the plain resolution recorded — entries can
    pass ``pallas=`` uniformly without special-casing the model."""
    name = name.lower()
    if name in ("vgg16", "vgg"):
        pallas = kwargs.pop("pallas", None)
        if pallas is not None:
            from distributed_training_pytorch_tpu.ops import dispatch

            dispatch.record(
                "vgg16",
                "conv",
                "plain",
                reason="no fused-kernel coverage (3x3 convs) — pallas knob is a no-op",
            )
        return VGG16(num_classes=num_classes, **kwargs)
    if name in ("resnet50", "resnet"):
        return ResNet50(num_classes=num_classes, **kwargs)
    if name in ("vit", "vit-b/16", "vit_b16", "vitb16"):
        return ViTB16(num_classes=num_classes, **kwargs)
    if name in ("convnext-l", "convnext_l", "convnextl", "convnext"):
        return ConvNeXtL(num_classes=num_classes, **kwargs)
    if name in ("convnext-tiny", "convnext_tiny"):
        return ConvNeXtTiny(num_classes=num_classes, **kwargs)
    if name in ("resnet18_slim", "resnet18-slim"):
        return ResNet18Slim(num_classes=num_classes, **kwargs)
    if name in ("vit_tiny", "vit-tiny"):
        return ViTTiny(num_classes=num_classes, **kwargs)
    raise ValueError(f"unknown model {name!r}")
