from distributed_training_pytorch_tpu.ops.losses import (  # noqa: F401
    cross_entropy_loss,
    softmax_cross_entropy_with_integer_labels,
    tied_cross_entropy_loss,
    weighted_mean,
)
from distributed_training_pytorch_tpu.ops.metrics import accuracy, top_k_accuracy  # noqa: F401


def __getattr__(name):
    # Lazy re-export: pulling in jax.experimental.pallas costs real import
    # time, and most ops consumers only want losses/metrics/schedules.
    if name in ("flash_attention", "make_attention_fn", "conv1x1_bn_act", "conv1x1_bn_act_diff"):
        from distributed_training_pytorch_tpu.ops import pallas

        return getattr(pallas, name)
    if name in ("pallas_from_env", "kernel_dispatch"):
        # The dispatch policy layer (ops/dispatch.py) is pure stdlib — cheap —
        # but kept lazy for symmetry; ``kernel_dispatch`` returns the module.
        from distributed_training_pytorch_tpu.ops import dispatch

        if name == "kernel_dispatch":
            return dispatch
        return getattr(dispatch, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from distributed_training_pytorch_tpu.ops.schedules import (  # noqa: F401
    multistep_lr,
    warmup_cosine_lr,
)
