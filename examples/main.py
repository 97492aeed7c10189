"""Entry point — capability twin of the reference ``main.py``.

Wires the logger, distributed setup, the example trainer with the reference's
configuration (labels [cat, dog, snake], 224x224, 300 epochs, global batch 16,
validate every 5 epochs saving best by ("accuracy", "geq"), save dir ./runs,
no snapshot — ``main.py:5-22``), trains, and tears down (``main.py:24-26``).
"""

import sys

sys.path.insert(0, ".")  # allow `python examples/main.py` from the repo root

from distributed_training_pytorch_tpu.utils import Logger, enable_compile_cache
from examples.example_trainer import ExampleTrainer

if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    logger = Logger("VGG16", "./runs/logfile.log")

    # Analog of ExampleTrainer.ddp_setup(backend="nccl") (``main.py:7``): a
    # no-op single-process; reads COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID
    # on multi-host pods (see run.sh).
    ExampleTrainer.distributed_setup()

    trainer = ExampleTrainer(
        train_path="./data/train",
        val_path="./data/val",
        labels=["cat", "dog", "snake"],
        height=224,
        width=224,
        max_epoch=300,
        batch_size=16,
        pin_memory=True,  # accepted for parity; async prefetch makes it moot
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=5,
        save_folder="./runs",
        snapshot_path=None,
        logger=logger,
    )

    trainer.train()

    ExampleTrainer.destroy_process()
