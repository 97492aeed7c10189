"""CPU only: four virtual devices, interpreted Pallas kernels, no compile
cache. Set before anything imports jax."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PALLAS"] = "1"  # the LM entry's knob: puts the (interpreted) flash kernel on the path off-TPU
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # tests leave nothing behind in the checkout
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
