"""Test bootstrap: force an 8-device virtual CPU platform.

SURVEY.md §4: multi-device semantics are tested without a pod via
``--xla_force_host_platform_device_count=8`` — real Mesh/jit/collective paths,
no TPU required. The setup lives in ``compat.force_host_devices`` (one
implementation shared with ``__graft_entry__.py`` and the CPU harnesses under
``scripts/``): it sets ``XLA_FLAGS``/``JAX_PLATFORMS`` and the ``jax_platforms``
config before the CPU client first initializes — which has not happened yet
at conftest import time.
"""

from distributed_training_pytorch_tpu import compat

compat.force_host_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


def pytest_configure(config):
    """Build the native input library (``data/native.py`` runs ``make -C csrc``
    on first use) once, before xdist starts its workers: on a fresh tree six
    workers would each start that build while collecting, and the tests that
    need the library would skip. A worker's config carries ``workerinput``."""
    if not hasattr(config, "workerinput"):
        from distributed_training_pytorch_tpu.data import native

        native.available()
