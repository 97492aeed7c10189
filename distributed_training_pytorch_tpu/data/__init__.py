from distributed_training_pytorch_tpu.data.dataset import (  # noqa: F401
    ArrayDataSource,
    ImageFolderDataSource,
    NativeImageFolderSource,
)
from distributed_training_pytorch_tpu.data import native  # noqa: F401
from distributed_training_pytorch_tpu.data.loader import ShardedLoader  # noqa: F401
from distributed_training_pytorch_tpu.data.records import (  # noqa: F401
    NativeRecordFileSource,
    NativeRecordTrainSource,
    RecordFileSource,
    RecordFileWriter,
    pack_image_folder,
    write_shards,
)
from distributed_training_pytorch_tpu.data.prefetch import (  # noqa: F401
    device_prefetch,
    device_prefetch_chained,
    epoch_units,
)
from distributed_training_pytorch_tpu.data.transforms import (  # noqa: F401
    IMAGENET_MEAN,
    IMAGENET_STD,
    eval_transform,
    train_transform,
)
