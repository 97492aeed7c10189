from distributed_training_pytorch_tpu.utils.compile_cache import (  # noqa: F401
    enable_compile_cache,
)
from distributed_training_pytorch_tpu.utils.logger import Logger  # noqa: F401
